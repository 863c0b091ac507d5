"""What a traffic kind takes from the PROGRAM in a traced run: its own
account of the run so far, steptrace's rows, and the artifacts it writes
beside the trace. Every function returns nothing where the program's
telemetry is off (an untraced run never calls them) or where the program
is from before these existed.
"""

from __future__ import annotations


def program_state() -> dict:
    """The program's own account of the run so far: ``spans`` {name:
    [seconds, count]}, ``compile_s`` {phase: seconds} and ``import_s``.
    Keys are absent where the program has nothing to say (telemetry off,
    or a program from before these existed)."""
    import deepspeed_tpu
    out: dict = {}
    imp = getattr(deepspeed_tpu, "IMPORT_SECONDS", None)
    if imp is not None:
        out["import_s"] = float(imp)
    from deepspeed_tpu.utils.telemetry_probe import active_telemetry
    tel = active_telemetry()
    if tel is None:
        return out
    tracer, reg = tel.get_tracer(), tel.get_registry()
    if tracer is not None:
        out["spans"] = {k: list(v) for k, v in tracer.totals().items()}
    if reg is not None:
        c = reg.counter("ds_compile_seconds_total")
        phases = {dict(ls).get("phase"): c.value(**dict(ls))
                  for ls in c.label_sets()}
        if phases:
            out["compile_s"] = phases
    return out


def step_rows(n: int) -> list:
    """steptrace's rows (``STEP_LOG_KEYS``) of the last ``n`` steps."""
    from deepspeed_tpu.utils.telemetry_probe import active_telemetry
    tel = active_telemetry()
    st = tel.get_step_recorder() if tel is not None else None
    if st is None:
        return []
    return [r.log_row() for r in st.completed()[-n:]]


def export(out_dir: str, prefix: str) -> dict:
    """Have the program write its artifacts beside the trace; returns the
    context keys that point at them."""
    from deepspeed_tpu.utils.telemetry_probe import active_telemetry
    tel = active_telemetry()
    if tel is None:
        return {}
    paths = tel.export_artifacts(out_dir, prefix=prefix)
    return {"op_scopes_path": paths.get("op_scopes")}
