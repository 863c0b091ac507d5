"""From a configuration file (HF key names, as published) to the
program's model, and back to the plain numbers the reference and the
FLOP counts use. The file is the configuration as it is run: after the
model is built its widths are compared with the file's, so a preset that
drifted from the published numbers fails the run instead of being
measured under the published name."""

from __future__ import annotations

# HF key -> attribute of the program's ModelConfig
_WIDTHS = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "sliding_window": "sliding_window",
    "num_hidden_layers": "num_layers",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def build_model(cfg_file: dict, rig: dict):
    """The program's model for this configuration. ``rig`` may carry
    ``model_overrides`` (the benchmark's own CPU tests shrink the model
    to the tiny preset; a real run never sets it)."""
    from deepspeed_tpu.models.base import get_model_class
    prog = cfg_file["program"]
    overrides = dict(prog.get("model_overrides", {}))
    overrides["num_layers"] = cfg_file["num_hidden_layers"]
    overrides["max_seq_len"] = cfg_file["max_position_embeddings"]
    preset = prog["preset"]
    if rig.get("tiny"):
        preset = "tiny"
        overrides.update(rig["tiny"])
    model = get_model_class(prog["model_type"])(size=preset, **overrides)
    if not rig.get("tiny"):
        c = model.config
        for hf_key, attr in _WIDTHS.items():
            want, got = cfg_file[hf_key], getattr(c, attr)
            if want != got:
                raise ValueError(
                    f"configuration {hf_key}={want!r} but the program's "
                    f"{prog['model_type']}/{preset} model has "
                    f"{attr}={got!r}")
    return model


def reference_model(cfg_file: dict, model) -> dict:
    """HF-keyed numbers of the model AS BUILT (equal to the file's in a
    real run; the tiny preset's in the benchmark's CPU tests)."""
    c = model.config
    return {hf_key: getattr(c, attr) for hf_key, attr in _WIDTHS.items()}
