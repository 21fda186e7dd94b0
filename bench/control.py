"""The control of ``correct``: the plain reference computed in the precision
below the one the configuration states (bfloat16 expert weights for the
stated float32, 16-bit frequency counters for the stated 32-bit ones),
put in the program's place, and compared with the reference as stated by
the same numbers a run compares.  Its readings are
the upper ends the limits are set below; it has to come out not correct.

    python3 bench/control.py --workload ycsb-a.1chip --rows 20000 --seeds 1 2 3

``--rows`` is the number of rounds at the cell's lanes that a run of the
cell replays (its warm-up and window).  Runs on whatever device JAX finds.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell: str, seed: int, rows: int, root: Path = ROOT) -> dict:
    """The control's numbers for one seed."""
    from bench import harness, loop
    _, cell_d, config, mix = harness.resolve(cell, root)
    system = harness.system(config["system"], root)
    seconds = (rows * config["lanes"]
               / float(mix["max_ops_per_s_per_node"])) + 1
    job = harness.Job(cell=cell_d, config=config, traffic=mix, seed=seed,
                      seconds=seconds, trace=False, root=root)
    tr, seeds, _ = system.traffic(job)
    got = system.replay(config, tr, *seeds, rows, control=True)
    want = system.replay(config, tr, *seeds, rows)
    return loop.compare(got, want, rows * tr.keys.shape[1],
                        config["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    for seed in args.seeds:
        checks = readings(args.workload, seed, args.rows)
        print(json.dumps({"seed": seed, "correct": all(
            c["value"] <= c["limit"] for c in checks.values()),
            "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
