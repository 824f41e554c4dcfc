"""The control of ``correct``: the plain reference of the cell's model
(``models/<model>.py``) computed one precision down (int4 weights), put in
the program's place, at a cell's own size:

    python chipbench/control.py --workload tile224k.backlog \
        --seeds 2147483701,2147483702,2147483703

For each seed it draws the cell's image pool, computes the reference's
int8 logits and the control's, and prints the numbers ``correct`` is
decided on, which the control has to fail.  It runs where JAX's default
device is (on the chip by hand; ``tests/test_reference.py`` holds the same
comparison at a test's size on the CPU).  The benchmark's runs do not run
it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    import jax
    from lib import harness, reference as R, traffic

    cell = harness.load_cell(ROOT, args.workload)
    cfg, model = cell.config, cell.model
    qm8 = model.quantize_model(cfg)
    qm4 = model.quantize_model(cfg, weight_bits=4)
    fwd8, fwd4 = model.int8_forward(qm8), model.int8_forward(qm4)
    for seed in [int(s) for s in args.seeds.split(",")]:
        images = traffic.pool_images(cfg, int(cell.mix["pool"]), seed)
        want = R.logits(fwd8, qm8.quantize_input(images))
        ctrl = R.logits(fwd4, qm4.quantize_input(images))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": jax.devices()[0].device_kind,
                          "control": R.compare(list(ctrl), want)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
