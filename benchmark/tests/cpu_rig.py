"""The benchmark's own test rig: run one cell end to end on the CPU at
the program's ``tiny`` preset (Pallas kernels interpreted), through
``run.main`` with a rig that only this file may build. There is no
switch for this on the command line: ``python benchmark/run.py`` on a
machine without a TPU fails.

    JAX_PLATFORMS=cpu python benchmark/tests/cpu_rig.py <cell> [trace]
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                    # benchmark/
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))   # checkout

# peaks of a chip that does not exist: a CPU run never yields a device
# number, this only lets the reducers run
FAKE_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
              "hbm_bytes": 1e10, "source": "benchmark/tests/cpu_rig.py"}


# the three keys ``run.main`` and ``lib/modelspec.py`` take from a rig:
# fake peaks (and with them leave to run off a TPU), the tiny model, the
# traffic shrunk with it. The widths are the ``tiny`` presets' own, named
# here so that a configuration that reaches its widths through
# ``model_overrides`` is shrunk too and keeps what no preset fixes (the
# rehearsal's 64 experts top-8)
RIG = {"peaks": FAKE_PEAKS,
       "tiny": {"max_seq_len": 128, "hidden_size": 64, "num_heads": 4,
                "num_kv_heads": 2, "intermediate_size": 128,
                "vocab_size": 512},
       "traffic_overrides": {"seq_len": 128, "trace_seconds": 1.0}}


if __name__ == "__main__":
    import run
    cell = sys.argv[1]
    trace = sys.argv[2] if len(sys.argv) > 2 else "0"
    seconds = sys.argv[3] if len(sys.argv) > 3 else "2"
    sys.exit(run.main(["--workload", cell, "--seed", "3000000019",
                       "--seconds", seconds, "--trace", trace],
                      rig=RIG))
