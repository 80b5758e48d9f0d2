#!/usr/bin/env python3
"""The control of ``correct``: the plain reference put in the program's
place and computed one precision lower (bfloat16 for the configurations'
float32), at the cell's own size, on the machine's own device. It has to
come out as NOT correct on every seed; this prints the numbers it gives
so that they can be set beside the sound runs' (which read 0).

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13

Not part of a benchmark run. Exits 0 when every seed's control failed
the comparison, 1 when one passed it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def control_once(cell_name: str, seed: int, bytes_per_rank=None) -> list:
    """The comparison's numbers for one seed's control."""
    import numpy as np

    from chipbench import check, generator, harness
    _bench, _cell, config, traffic, coll = harness.load_cell(cell_name)
    dtype = np.dtype(config["dtype"])
    nbytes = int(bytes_per_rank or traffic["bytes_per_rank"])
    inputs = [generator.make_input(traffic, seed, r,
                                     nbytes // dtype.itemsize, dtype)
              for r in range(int(config["ranks"]))]
    return check.compare_results(
        f"control (bfloat16) seed {seed}", coll.lower_precision(inputs),
        coll.reference(inputs))


def main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import jax

    from chipbench import check
    d = jax.devices()[0]
    print(f"control of {args.workload} on {d.platform} {d.device_kind}",
          flush=True)
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        compared = control_once(args.workload, seed)
        check.report(compared)
        passed += check.verdict(compared)
    print(f"control: {passed} seed(s) passed the comparison (has to be 0)")
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
