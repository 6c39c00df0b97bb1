"""Data plane: the window's engine steps (``RealEngine.step_samples``),
their summed wall time over their count. ``step_ms.<cell kind>`` variants
read the same."""


def read(run):
    if not run.step_walls:
        return None
    return sum(run.step_walls) / len(run.step_walls) * 1e3
