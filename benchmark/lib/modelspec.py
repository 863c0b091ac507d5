"""From a configuration file (published key names) to the program's model,
and back to the plain numbers the reference and the FLOP counts use. The
file is the configuration as it is run: after the model is built its
widths are compared with the file's through the architecture's ``WIDTHS``,
so a preset that drifted from the published numbers fails the run instead
of being measured under the published name."""

from __future__ import annotations


def build_model(cfg_file: dict, arch, rig: dict):
    """The program's model for this configuration. ``rig`` may carry
    ``tiny`` (the benchmark's own CPU tests shrink the model to the tiny
    preset; a real run never sets it). The widths are then the preset's
    and not the file's, unless the file itself runs that preset."""
    from deepspeed_tpu.models.base import get_model_class
    prog = cfg_file["program"]
    overrides = dict(prog.get("model_overrides", {}))
    overrides["num_layers"] = cfg_file["num_hidden_layers"]
    overrides["max_seq_len"] = cfg_file["max_position_embeddings"]
    preset = prog["preset"]
    shrunk = bool(rig.get("tiny")) and preset != "tiny"
    if rig.get("tiny"):
        preset = "tiny"
        overrides.update(rig["tiny"])
    model = get_model_class(prog["model_type"])(size=preset, **overrides)
    for key, attr in ({} if shrunk else arch.WIDTHS).items():
        if key not in cfg_file:
            if key in arch.OPTIONAL:
                continue
            raise ValueError(
                f"architecture {cfg_file['architecture']!r} demands "
                f"{key!r} of the configuration file, which lacks it")
        want, got = cfg_file[key], getattr(model.config, attr)
        if want != got:
            raise ValueError(
                f"configuration {key}={want!r} but the program's "
                f"{prog['model_type']}/{preset} model has {attr}={got!r}")
    return model


def reference_model(arch, model, check: dict | None = None) -> dict:
    """Published-key numbers of the model AS BUILT (equal to the file's in
    a real run; the tiny preset's in the benchmark's CPU tests). With the
    configuration's ``check``, also the keys of it that the architecture
    declares in ``CHECK_KEYS``: what its reference needs to say which
    positions it can vouch for."""
    m = {key: getattr(model.config, attr)
         for key, attr in arch.WIDTHS.items()}
    if check is not None:
        m.update({key: check[key] for key in getattr(arch, "CHECK_KEYS", ())})
    return m
