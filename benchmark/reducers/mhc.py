"""What a stack with manifold-constrained hyper-connections counts (PR 56):
the program's gauge ``ds_mhc_sinkhorn_residual``, read from its telemetry
registry at the end of a traced run, as ``reducers/moe.py`` reads the held
experts' counters. The compiled step returns the residual as a device
scalar whether the run is traced or not; with telemetry on, the engine
feeds the registry from the step before the one it has just dispatched
(``deepspeed_tpu/models/xing4.py`` ``record_step_metrics``), so no host
callback sits in the program. None where the program has no such gauge (a
program from before it, or a model without such streams)."""

from __future__ import annotations

from lib.reducers import reducer

GAUGE = "ds_mhc_sinkhorn_residual"


@reducer
def mhc_sinkhorn_residual(ctx, args):
    """The largest ``|rowsum - 1|`` or ``|colsum - 1|`` of any ``H_res`` of
    any step the registry has seen; None where nothing was recorded."""
    try:
        from deepspeed_tpu.utils.telemetry_probe import active_telemetry
        tel = active_telemetry()
        reg = tel.get_registry() if tel is not None else None
        gauge = reg.get(GAUGE) if reg is not None else None
        return None if gauge is None else gauge.value()
    except Exception:       # a program without it: nothing to read
        return None
