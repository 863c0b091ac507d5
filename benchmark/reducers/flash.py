"""What the flash kernels' dispatch says of a row it could not hold whole
(PR 64): the program's gauge ``ds_flash_segments{s, d}``
(``deepspeed_tpu/ops/pallas/flash_attention.py`` ``_gauge_segments``), set
at trace time where a call is built and read from the telemetry registry
at the end of a traced run, as ``reducers/mhc.py`` reads its gauge. None
where the program has no such gauge (a program from before it, or a run
whose step was not traced in this process)."""

from __future__ import annotations

from lib.reducers import reducer

GAUGE = "ds_flash_segments"


@reducer
def flash_segments(ctx, args):
    """The most equal spans any flash call of the run cut a (batch x head)
    row in: 1 where every row was held whole; None where nothing was
    recorded."""
    try:
        from deepspeed_tpu.utils.telemetry_probe import active_telemetry
        tel = active_telemetry()
        reg = tel.get_registry() if tel is not None else None
        gauge = reg.get(GAUGE) if reg is not None else None
        if gauge is None:
            return None
        spans = [gauge.value(**labels) for labels in gauge.label_sets()]
        return max(spans) if spans else None
    except Exception:       # a program without it: nothing to read
        return None
