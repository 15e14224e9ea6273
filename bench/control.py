"""The readings behind the limit of ``correct``, on the chip at a cell's size.

    python3 bench/control.py --workload <cell> --seeds 1-12 --control-seeds 1-3

For each seed, the weights and the cell's pool of inputs are made as a run
makes them, and ``logits_rel_err`` is read twice: for the program's forward
(the lower reading: the largest over the seeds) and, on the control seeds,
for the control, the plain reference put in the program's place and computed
in the precision next below the configuration's float32 at HIGHEST: three
bf16 passes, as XLA's ``Precision.HIGH`` makes them (the upper reading: the
smallest over the seeds).  The limit goes between the two.  One JSON line per
reading; runs in one process so that set-up is paid once.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def seeds(text: str) -> list[int]:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def control_forward(cell):
    """The reference at three bf16 passes, in the program's place."""
    import jax
    return jax.jit(lambda p, x: cell.model.reference(cell.config, p, x, "bf16x3"))


def reading(cell, seed: int, forward) -> float:
    """``logits_rel_err`` of ``forward`` over the cell's pool for ``seed``."""
    import jax
    from bench import harness
    key = harness.seed_key(seed)
    traffic = cell.traffic
    params = cell.model.build(cell.config, jax.random.fold_in(key, 0))
    batches = cell.model.inputs(cell.config, jax.random.fold_in(key, 1),
                                traffic["pool"], traffic["batch"])
    outputs = [(j, forward(params, x)) for j, x in enumerate(batches)]
    return harness.check(cell, params, batches, outputs)["logits_rel_err"][0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=seeds("1-12"))
    ap.add_argument("--control-seeds", type=seeds, default=seeds("1-3"))
    args = ap.parse_args(argv)
    from bench import harness
    from repro.runtime.compile_cache import use_compilation_cache
    import jax
    use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    dev = jax.devices()[0]
    program, control = cell.model.program_forward(), control_forward(cell)
    for name, fwd, which in (("program", program, args.seeds),
                             ("control", control, args.control_seeds)):
        for seed in which:
            print(json.dumps({"workload": cell.name, "forward": name, "seed": seed,
                              "logits_rel_err": reading(cell, seed, fwd),
                              "device": dev.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
