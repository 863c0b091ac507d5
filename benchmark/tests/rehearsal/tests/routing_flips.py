"""Where does a stand-in's routing flip, and what would each margin leave?
The tool the routed check's numbers are SET from (``control.py`` then
proves them): on the engine's own master weights, the float32 ``mixtral``
reference and the same reference with every weight matmul's operands
rounded to ``--as`` (``control.weight_matmuls_in``), the positions of the
tail where the two chose another expert set, the largest float32 gap
(k-th to (k+1)-th router logit, as a share of the layer's logit rms) at
which that happened, and for each margin of ``MARGINS`` the share left
out and the two logits errors over the rest.

    chiprun -- python3 benchmark/tests/routing_flips.py <cell> --as bfloat16 <seed> [<seed> ...]

One JSON line a seed (a new process each). Part of the rehearsal: it is
added to a copy of ``benchmark/`` with the other files of
``tests/rehearsal/`` and reads the ``mixtral`` module's ``routes``.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE),
                HERE]

MARGINS = (0.0, 0.005, 0.01, 0.015, 0.02, 0.025, 0.03, 0.035, 0.04, 0.05,
           0.06)


def flips(cell_name: str, seed: int, rig: dict, stand_in: str) -> dict:
    import control
    import jax
    import jax.numpy as jnp
    import numpy as np
    from kinds import train_job
    cell, m, master, toks, tgts = control.staged(cell_name, seed, rig)
    arch, tail = cell["arch"], train_job.TAIL
    with jax.default_matmul_precision("highest"):
        _, ref_tail, _ = arch.reference(master, toks, tgts, m, tail)
        gap, sets, scales = arch.routes(master, toks, m, tail)
        with control.weight_matmuls_in(stand_in):
            _, got_tail, _ = arch.reference(master, toks, tgts, m, tail)
            _, got_sets, _ = arch.routes(master, toks, m, tail)
    flipped = np.asarray(jnp.any(sets != got_sets, axis=(0, -1)))
    gap = np.asarray(gap)
    out = {"cell": cell_name, "seed": seed, "stand_in": stand_in,
           "positions": int(gap.size), "flipped": int(flipped.sum()),
           "largest_flipped_gap": float(gap[flipped].max(initial=0.0)),
           "router_logit_rms": [float(x) for x in scales],
           "gap_quartiles": [float(x) for x in np.quantile(
               gap, (0.25, 0.5, 0.75))],
           "by_margin": {}}
    for margin in MARGINS:
        counted = jnp.asarray(gap >= margin)
        row = train_job.tail_numbers(got_tail, ref_tail, counted)
        row["flipped_and_counted"] = int((flipped & (gap >= margin)).sum())
        out["by_margin"][str(margin)] = row
    if flipped.any():
        out["flipped_only"] = train_job.tail_numbers(
            got_tail, ref_tail, jnp.asarray(flipped))
    return out


if __name__ == "__main__":
    import control
    control.cli(flips, __file__, __doc__, "bfloat16")
