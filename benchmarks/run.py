# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows (see each module's docstring for the paper mapping).

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller traces (CI mode)")
    ap.add_argument("--only", default="",
                    help="comma-separated module subset")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (ablation, adaptivity, algorithms, efficiency,
                            elasticity, fc_sweep, resources, roofline_table,
                            sizes, tenants, throughput)
    modules = [
        ("elasticity", elasticity),       # Figs. 1, 13
        ("efficiency", efficiency),       # Figs. 2, 14, 15
        ("throughput", throughput),       # hot path: reference vs fused
        ("adaptivity", adaptivity),       # Figs. 16-19
        ("sizes", sizes),                 # byte hit rate: sized traces
        ("tenants", tenants),             # multi-tenant isolation (§11)
        ("resources", resources),         # Figs. 20-22
        ("algorithms", algorithms),       # Fig. 23, Table 3
        ("ablation", ablation),           # Fig. 24
        ("fc_sweep", fc_sweep),           # Fig. 25
        ("roofline", roofline_table),     # §Dry-run / §Roofline
    ]
    only = set(filter(None, args.only.split(",")))
    valid = {name for name, _ in modules}
    unknown = only - valid
    if unknown:
        # A typo'd --only used to silently run nothing and exit green —
        # fail loudly instead, listing the registry.
        print(f"run.py: unknown --only module(s): {sorted(unknown)}",
              file=sys.stderr)
        print(f"run.py: valid modules: {sorted(valid)}", file=sys.stderr)
        sys.exit(2)
    failures = 0
    for name, mod in modules:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            mod.run(quick=args.quick)
            print(f"# {name}: done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # keep the suite going
            failures += 1
            import traceback
            traceback.print_exc()
            print(f"# {name}: FAILED {type(e).__name__}: {e}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
