"""Compile-only check of an arch's serve step (prefill or decode) on the
production mesh. Serving itself is ``repro.serving.server``.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --shape decode_32k --dry-run
"""
import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--dry-run", action="store_true", required=True,
                    help="lower+compile only, on 512 virtual host devices")
    args = ap.parse_args()

    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512").strip()
    from repro.launch.dryrun import dry_run_one
    rec = dry_run_one(args.arch, args.shape, multi_pod=args.multi_pod)
    sys.exit(0 if rec["status"] in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()
