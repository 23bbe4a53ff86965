# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
#
#   python benchmarks/run.py [filter] [--fast] [--events PATH]
#
# ``--fast`` is the CI smoke mode: every suite shrinks to one grid cell and a
# handful of iterations, so the whole file finishes in well under a minute.
# ``--events PATH`` mirrors every CSV row into a schema-checked JSONL event
# log (repro.obs ``bench`` events) and wraps each suite in a profiling span,
# so benchmark runs land in the same sink the sweeps use.
from __future__ import annotations

import os
import sys
import traceback

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> None:
    from benchmarks import dist_bench, fault_bench, kernel_bench, paper_figs

    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import EventLog, span

    enable_compile_cache()
    args = [a for a in sys.argv[1:]]
    fast = "--fast" in args
    if fast:
        args.remove("--fast")
        dist_bench.FAST = True
        paper_figs.FAST = True
        kernel_bench.FAST = True
        fault_bench.FAST = True
    events_path = None
    if "--events" in args:
        i = args.index("--events")
        events_path = args[i + 1]
        del args[i:i + 2]
    only = args[0] if args else None

    log = EventLog(events_path) if events_path else None
    if log is not None:
        log.start(config={"fast": fast, "filter": only},
                  fingerprint=f"bench:{'fast' if fast else 'full'}")

    suites = paper_figs.ALL + kernel_bench.ALL + dist_bench.ALL + fault_bench.ALL
    print("name,us_per_call,derived")
    failures = 0
    for suite in suites:
        if only and only not in suite.__name__:
            continue
        try:
            with span(f"bench/{suite.__name__}"):
                rows = list(suite())
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}")
                if log is not None:
                    log.emit("bench", name=name, value=float(us),
                             unit="us_per_call", derived=str(derived))
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"{suite.__name__},NaN,ERROR")
    if log is not None:
        log.end(status="fail" if failures else "ok")
        log.close()
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
