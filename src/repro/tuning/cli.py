"""Tuning-cache CLI.

    python -m repro.tuning warm [--full] [--force]   # pre-tune registered shapes
    python -m repro.tuning show                      # dump cached timing tables
    python -m repro.tuning clear                     # drop the cache

``warm`` drives every registered benchmark shape (repro.tuning.shapes)
through the eager ``block="auto"`` paths, so the measurement protocol
runs and winners persist; already-cached shapes are fast no-ops unless
``--force`` clears the cache first.
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.compile_cache import use_compile_cache


def _cache():
    from repro.tuning.cache import TuningCache

    return TuningCache()


def cmd_warm(args: argparse.Namespace) -> int:
    from repro.tuning import session as sess_mod
    from repro.tuning.shapes import REGISTRY

    cache = _cache()
    if args.force:
        cache.clear()
    print(f"tuning cache: {cache.file}")
    before = set(cache.items())
    for entry in REGISTRY:
        t0 = time.perf_counter()
        try:
            entry.run(args.full)
        except Exception as e:  # keep warming the rest
            print(f"  {entry.name:32s} FAILED: {type(e).__name__}: {e}")
            continue
        dt = time.perf_counter() - t0
        print(f"  {entry.name:32s} ok ({dt:.1f}s)")
    fresh = {
        k: r for k, r in _cache().items().items() if k not in before
    }
    print(
        f"{len(fresh)} new record(s), "
        f"{sess_mod.MEASURE_COUNT} measurement(s) taken"
    )
    _show_records(fresh or _cache().items())
    return 0


def _show_records(records) -> None:
    from repro.tuning.cache import format_block

    for key in sorted(records):
        rec = records[key]
        print(f"\n{key}")
        depth = f" @f{rec.fuse_steps}" if rec.fuse_steps != 1 else ""
        strat = (
            f" -> {rec.strategy_resolved}" if rec.strategy_resolved else ""
        )
        print(
            f"  best block: {format_block(rec.block)}{depth}{strat}  "
            f"[{rec.source}]"
        )
        winner = rec.winner_label
        for blk, us in sorted(rec.timings_us.items(), key=lambda kv: kv[1]):
            mark = " <-- winner" if blk == winner else ""
            print(f"    {blk:>16s}  {us:12.1f} us{mark}")
        for blk, err in sorted(rec.failed.items()):
            print(f"    {blk:>16s}  FAILED: {err}")


def cmd_show(args: argparse.Namespace) -> int:
    cache = _cache()
    records = cache.items()
    print(f"tuning cache: {cache.file} ({len(records)} record(s))")
    _show_records(records)
    return 0


def cmd_clear(args: argparse.Namespace) -> int:
    cache = _cache()
    n = len(cache.items())
    cache.clear()
    print(f"cleared {n} record(s) from {cache.file}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.tuning", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    sub = ap.add_subparsers(dest="cmd", required=True)
    warm = sub.add_parser("warm", help="pre-tune registered benchmark shapes")
    warm.add_argument("--full", action="store_true",
                      help="paper-sized shapes (slow)")
    warm.add_argument("--force", action="store_true",
                      help="clear the cache first (re-measure everything)")
    warm.set_defaults(fn=cmd_warm)
    show = sub.add_parser("show", help="dump cached timing tables")
    show.set_defaults(fn=cmd_show)
    clear = sub.add_parser("clear", help="delete the cache")
    clear.set_defaults(fn=cmd_clear)
    args = ap.parse_args(argv)
    use_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
