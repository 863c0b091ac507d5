"""Model family breadth (reference:
inference/v2/model_implementations/{falcon,opt,phi,phi3,qwen,qwen2,
qwen2-moe,mistral,llama_v2,mixtral}/)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import (GPT2, OPT, Bloom, Falcon, GPTJ, GPTNeoX,
                                  GraniteHybrid, InternLM, Llama, Mellum,
                                  Mistral, Mixtral, Ouro, Phi, Phi3, Qwen,
                                  Qwen2, Qwen2MoE, get_model_class)

FAMILIES = [GPT2, Llama, Mistral, Mixtral, Falcon, OPT, Phi, Phi3, Qwen,
            Qwen2, Qwen2MoE, Bloom, GPTJ, GPTNeoX, InternLM]


def tiny(cls):
    return cls(size="tiny")


@pytest.mark.parametrize("cls", FAMILIES)
def test_family_init_loss_decode(cls):
    """Every family initializes, computes a loss, and decodes with a KV
    cache whose logits agree with the parallel forward."""
    model = tiny(cls)
    params = model.init(jax.random.PRNGKey(0))
    # num_params accounting matches the real tree
    n_actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    assert model.config.num_params() == n_actual, cls.__name__
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 512)
    loss = model.loss(params, (tokens[:, :-1], tokens[:, 1:]))
    assert jnp.isfinite(loss)
    # prefill logits == full forward logits
    logits_fwd = model.apply(params, tokens[:, :-1])
    cache = model.init_cache(2, 32)
    logits_dec, cache = model.decode(params, tokens[:, :-1], cache)
    np.testing.assert_allclose(np.asarray(logits_fwd),
                               np.asarray(logits_dec), rtol=2e-2,
                               atol=2e-3)
    assert int(cache["index"]) == 16


# families that train and refuse to decode: a stack of kinds has no single
# block(), a looped stack would need a cache slot a pass and layer
TRAIN_ONLY = [GraniteHybrid, Mellum, Ouro]


@pytest.mark.parametrize("cls", TRAIN_ONLY)
def test_train_only_family_init_loss_rules_and_refusal(cls):
    """The tiny preset initializes, its analytic parameter count is the
    tree's, the partition rules lay every leaf out (tests/test_ouro.py
    holds a looped stack's to a rule for EVERY leaf, its two output norms a
    layer and its gate among them), the loss is finite, and the serving
    entry points refuse."""
    from deepspeed_tpu.parallel.partition import match_rules
    model = tiny(cls)
    params = model.init(jax.random.PRNGKey(0))
    n_actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    assert model.config.num_params() == n_actual, cls.__name__
    match_rules(model.partition_rules(), params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, 512)
    # jitted: eager, every line of a stack of kinds compiles alone
    loss = jax.jit(model.loss)(params, (tokens[:, :-1], tokens[:, 1:]))
    assert jnp.isfinite(loss)
    assert jax.jit(model.apply)(params, tokens[:, :-1]).shape == (
        2, 128, 512)
    for entry in (model.decode, model.init_cache):
        with pytest.raises(NotImplementedError):
            entry()


def test_registry_covers_reference_families():
    for name in ("gpt2", "llama", "mistral", "mixtral", "falcon", "opt",
                 "phi", "phi3", "qwen", "qwen2", "qwen2_moe", "bloom",
                 "gptj", "gptneox", "internlm", "bert", "ouro"):
        assert get_model_class(name) is not None


def test_bert_encoder_end_to_end(devices8):
    """Encoder family (reference: the BERT training-kernel workload +
    module_inject/containers/bert.py): MLM init -> loss -> 3 engine
    steps with decreasing loss, masked positions ignored, and padding
    masked out of attention."""
    from deepspeed_tpu.models import Bert
    model = Bert(size="tiny")
    params = model.init(jax.random.PRNGKey(0))
    n_actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    assert n_actual == model.config.num_params()
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 512, (8, 32))
    targets = np.where(rng.random((8, 32)) < 0.15, tokens, -100)
    mask = np.ones((8, 32), np.int32)
    mask[:, 28:] = 0                       # padding tail
    loss0 = model.loss(params, (tokens, targets, mask))
    assert jnp.isfinite(loss0)
    # padding tokens must not influence real positions
    tokens2 = tokens.copy()
    tokens2[:, 30] = (tokens2[:, 30] + 5) % 512
    l1 = model.apply(params, tokens, attention_mask=mask)
    l2 = model.apply(params, tokens2, attention_mask=mask)
    np.testing.assert_allclose(np.asarray(l1[:, :28]),
                               np.asarray(l2[:, :28]), atol=1e-5)
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2}, "mesh": {"fsdp": -1},
        "steps_per_print": 10 ** 9})
    losses = [float(engine.train_batch((tokens, targets, mask)))
              for _ in range(3)]
    assert losses[-1] < losses[0], losses


def test_bloom_alibi_extends_past_train_length():
    """ALiBi's point: no learned/rotary position table, so a model
    scored at a longer context than tiny's 128 still produces finite,
    position-sensitive logits, and nearby keys dominate far ones."""
    model = Bloom(size="tiny", max_seq_len=256)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 200), 0, 512)
    logits = model.apply(params, tokens)
    assert bool(jnp.isfinite(logits).all())
    # perturbing a FAR token moves the last position's logits less than
    # perturbing a NEAR token (the linear-bias recency prior)
    far = tokens.at[0, 0].set((tokens[0, 0] + 7) % 512)
    near = tokens.at[0, 198].set((tokens[0, 198] + 7) % 512)
    d_far = float(jnp.max(jnp.abs(
        model.apply(params, far)[0, -1] - logits[0, -1])))
    d_near = float(jnp.max(jnp.abs(
        model.apply(params, near)[0, -1] - logits[0, -1])))
    assert d_near > d_far


def test_gptneox_decode_parity_with_trained_norms():
    """KV-cache decode must match apply() when ln1 != ln2 — at init both
    norms are identity so the family parity test can't see a decode path
    that feeds the wrong norm into the MLP."""
    model = GPTNeoX(size="tiny")
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(9)
    params["layers"]["ln2_scale"] = (
        params["layers"]["ln2_scale"]
        * (1.0 + 0.3 * jax.random.normal(
            key, params["layers"]["ln2_scale"].shape)))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 512)
    ref = model.apply(params, tokens)
    cache = model.init_cache(2, 32)
    dec, _ = model.decode(params, tokens, cache)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(dec),
                               rtol=2e-2, atol=2e-3)


def test_bloom_and_neox_through_v2_match_forward():
    """v2 paged serving must reproduce the model's own forward for the
    newly supported families: Bloom (ALiBi bias in the paged path) and
    GPT-NeoX (dual-norm parallel residual), with non-identity norms."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    for cls in (Bloom, GPTNeoX):
        model = cls(size="tiny")
        e = InferenceEngineV2(model, RaggedInferenceEngineConfig(
            dtype="float32", kv_block_size=8, num_kv_blocks=64,
            max_chunk_size=16))
        if "ln2_scale" in e.params["layers"]:
            e.params["layers"]["ln2_scale"] = (
                e.params["layers"]["ln2_scale"]
                * (1.0 + 0.3 * jax.random.normal(
                    jax.random.PRNGKey(9),
                    e.params["layers"]["ln2_scale"].shape)))
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(2), (12,), 0, 512)).tolist()
        logits = e.put([0], [prompt])
        ref = model.apply(e.params, jnp.asarray([prompt]))
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(ref[0, -1]),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=cls.__name__)


def test_gptneox_dual_norm_parallel_residual():
    """NeoX: attention and MLP read DIFFERENT norms of the same input;
    scaling ln2 must change the output while a single-norm parallel
    model (GPT-J) has no ln2 at all."""
    model = GPTNeoX(size="tiny")
    params = model.init(jax.random.PRNGKey(0))
    assert "ln2_scale" in params["layers"]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 512)
    base = model.apply(params, tokens)
    params["layers"]["ln2_scale"] = params["layers"]["ln2_scale"] * 2.0
    assert float(jnp.max(jnp.abs(model.apply(params, tokens) - base))) > 0
    gptj = GPTJ(size="tiny").init(jax.random.PRNGKey(0))
    assert "ln2_scale" not in gptj["layers"]
    # GPT-J bias layout: unbiased attention, biased MLP
    assert "wq_b" not in gptj["layers"] and "w_up_b" in gptj["layers"]


def test_mistral_sliding_window_masks_far_keys():
    """Tokens beyond the window must not affect the current position —
    perturbing history outside the window leaves logits unchanged."""
    # one layer: receptive field of the last position is exactly the
    # window (with L layers it grows to L*window, which is why the full
    # tiny preset wouldn't show masking over 64 tokens)
    model = Mistral(size="tiny", num_layers=1)
    params = model.init(jax.random.PRNGKey(0))
    t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, 512)
    t2 = t1.at[:, :16].set(0)  # change tokens > window away from the end
    l1 = model.apply(params, t1)
    l2 = model.apply(params, t2)
    np.testing.assert_allclose(np.asarray(l1[:, -1]),
                               np.asarray(l2[:, -1]), rtol=1e-4,
                               atol=1e-5)
    # but nearby history does matter
    t3 = t1.at[:, 60].set((t1[0, 60] + 1) % 512)
    l3 = model.apply(params, t3)
    assert np.abs(np.asarray(l1[:, -1]) - np.asarray(l3[:, -1])).max() > 1e-6
    # the KV-cache decode path applies the same window: prefill logits
    # beyond the window must match the parallel forward
    cache = model.init_cache(1, 64)
    l_dec, _ = model.decode(params, t1, cache)
    np.testing.assert_allclose(np.asarray(l1[:, -1]),
                               np.asarray(l_dec[:, -1]), rtol=2e-2,
                               atol=2e-3)


def test_parallel_residual_families_through_v2_factory():
    """Falcon/Phi (parallel residual) must run the paged v2 path
    (regression: paged_forward once assumed ln2 exists)."""
    from deepspeed_tpu.inference.v2 import build_engine
    for name in ("falcon", "phi"):
        eng = build_engine(name, size="tiny",
                           engine_config={"num_kv_blocks": 16})
        eng.put([0], [[1, 2, 3]])


def test_falcon_parallel_residual_structure():
    model = Falcon(size="tiny")
    params = model.init(jax.random.PRNGKey(0))
    assert "ln2_scale" not in params["layers"]  # single shared input norm
    assert model.config.num_kv_heads == 1       # multi-query attention


def test_qwen2_moe_shared_expert_contributes():
    model = Qwen2MoE(size="tiny")
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 512)
    base = model.apply(params, tokens)
    params2 = params.copy()
    params2["layers"] = dict(params["layers"])
    params2["layers"]["shared"] = jax.tree.map(
        jnp.zeros_like, params["layers"]["shared"])
    off = model.apply(params2, tokens)
    assert np.abs(np.asarray(base) - np.asarray(off)).max() > 1e-6


def test_qwen2_moe_quantized_shared_expert():
    """quantize_weights=True used to KeyError at trace time on
    Qwen2-MoE (ADVICE r5): quantize_dense_params walks layers/shared
    into w_gate_q/w_up_q/w_down_q, so _mlp must dequantize the shared
    subtree at its use site like the routed experts dict does.
    min_size is lowered so the tiny model's shared matrices actually
    quantize (real-scale models clear the default threshold)."""
    from deepspeed_tpu.linear.quantization import quantize_dense_params
    model = Qwen2MoE(size="tiny")
    params = model.init(jax.random.PRNGKey(0))
    qparams = quantize_dense_params(params, min_size=1024)
    # the shared subtree really is quantized (fix must not just skip it)
    assert "w_gate_q" in qparams["layers"]["shared"]
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 512)
    a = np.asarray(model.apply(qparams, tok))       # KeyError before fix
    b = np.asarray(model.apply(params, tok))
    rel = np.abs(a - b).max() / max(np.abs(b).max(), 1e-9)
    assert rel < 0.05, rel


def test_inference_v2_factory_dispatch():
    """reference: engine_factory.py build_hf_engine model_type table."""
    from deepspeed_tpu.inference.v2 import (SUPPORTED_MODEL_TYPES,
                                            build_engine)
    assert "qwen2_moe" in SUPPORTED_MODEL_TYPES
    eng = build_engine("mistral", size="tiny",
                       engine_config={"num_kv_blocks": 16})
    toks = [1, 2, 3]
    eng.put([0], [toks])
    with pytest.raises(ValueError):
        build_engine("not_a_model")


def test_family_trains_through_engine(devices8):
    """A couple of the new families through the full engine path."""
    for cls in (Falcon, Qwen2MoE):
        from deepspeed_tpu.parallel import mesh as m
        m.reset_topology()
        engine, _, _, _ = ds.initialize(
            model=tiny(cls),
            config={"train_batch_size": 16,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "steps_per_print": 100, "mesh": {"fsdp": -1},
                    "zero_optimization": {"stage": 3}})
        tokens = jax.random.randint(jax.random.PRNGKey(0), (16, 17), 0, 512)
        batch = (tokens[:, :-1], tokens[:, 1:])
        losses = [float(engine.train_batch(batch)) for _ in range(3)]
        assert losses[-1] < losses[0], (cls.__name__, losses)


# ---- a family owns its fields and its arithmetic (PR 45) -------------------
# num_params(), num_active_params(), flops_per_token(max_seq_len) and
# flops_per_token(max_seq_len, causal=False) as the parent commit (PR 44,
# 007653c) printed them, where ``ModelConfig`` held every family's fields
# and three hand-written sums: one walk over a stack's kinds has to give the
# same. A ``cell/<file>`` case is the model of that configuration file of
# ``benchmark/configs/`` (its preset, ``model_overrides``, depth and
# sequence).
PARENT_COUNTS = {
    "kimi_linear/tiny": (6576944, 482096, 2831616.0, 2892576),
    "kimi_linear/48b-a3b": (49122763648, 3484541824, 22354500864.0,
                            25877501184),
    "granite_hybrid/tiny": (280992, 280992, 1833792.0, 1882560),
    "granite_hybrid/4.0-h-micro": (3191396096, 3191396096, 25817369088.0,
                                   32259770880),
    "mellum/tiny": (1753664, 377408, 2296512.0, 2854272),
    "mellum/12b-a2.5b": (12149915904, 2439053568, 36876957120.0,
                         193663993344),
    # PR 46's own, pinned when the family came (ISSUE 46's 625,667,136)
    "qwen3_next/tiny": (12890056, 552904, 3275184.0, 3372720),
    "qwen3_next/80b-a3b": (79674391296, 3874929408, 99032031744.0,
                           176341148160),
    "cell/qwen3-next-80b-ep16-zero3-1chip": (
        625667136, 230878272, 1582885248.0, 1985513856),
    # PR 54's own, pinned when the family came (ISSUE 54's 469,284,992 and
    # the four routed layers' expert biases of 64)
    "lfm2_moe/tiny": (1726176, 251616, 1566912.0, 1615680),
    "lfm2_moe/24b-a2b": (23843661440, 2326881920, 29691897600.0,
                         45420414720),
    "cell/lfm2-24b-ep8-zero3-1chip": (
        469285248, 186169728, 1217939712.0, 1318590720),
    # PR 56's own, pinned when the family came (ISSUE 56's 631 M: layers 2
    # to 5, phi, b and alpha of eight sublayers among them)
    "xing4_0/tiny": (1839846, 365286, 2396868.0, 2701668),
    "xing4_0/29b-a4b": (29505505264, 4402595824, 345762066336.0,
                        667883384736),
    "cell/xing4.0-29b-ep8-zero3-1chip": (
        631149528, 300848088, 2463651600.0, 3470161680),
    # PR 60's own, pinned when the family came (ISSUE 60's 653,577,216:
    # layers 1 to 4, the head count a kind)
    "laguna/tiny": (6621504, 575808, 3650784.0, 4536192),
    "laguna/s-2.1": (117561953280, 8449231872, 514740548556.0,
                     5151266850816),
    "cell/laguna-s-2.1-ep32-zero3-1chip": (
        653577216, 363383808, 2415689856.0, 5270980608),
    # PR 64's own, pinned when the family came (ISSUE 64's 511,857,152:
    # layers 1 to 4, 16 of 128 experts held, an eighth of the vocabulary)
    "deepseek_v3/tiny": (3403616, 405344, 2545056.0, 2849856),
    "deepseek_v3/kanana-2-30b-a3b": (30670815104, 3614408576, 68430298368.0,
                                     116747205888),
    "cell/kanana-2-30b-ep8-zero3-1chip": (
        511857152, 224023040, 5173791744.0, 9200200704),
    # PR 66's own, pinned when the family came (ISSUE 66's 773,582,304:
    # layers 26 to 36, a quarter of each mixer's heads, 8 of 512 experts
    # held, an eighth of the vocabulary)
    "nemotron_h/tiny": (1282608, 279088, 1576608.0, 1625376),
    "nemotron_h/3-super-120b-a12b": (120668707840, 12770237440,
                                     125443319808.0, 176982730752),
    "cell/nemotron-3-super-120b-ep64-zero3-1chip": (
        773582304, 562843104, 3040471872.0, 3090797376),
    "ouro/tiny": (148097, 148097, 3360792.0, 3750936),
    "ouro/2.6b": (2667974657, 2667974657, 216840634392.0, 371457097752),
    "cell/kimi-linear-48b-ep32-zero3-1chip": (
        602450816, 383036288, 2556198144.0, 3059483904),
    "cell/granite-4.0-h-micro-zero3-1chip": (
        772160448, 772160448, 4790261376.0, 4890912384),
    "cell/mellum2-12b-ep4-zero3-1chip": (
        595153152, 248336640, 1699239936.0, 4371506688),
    "cell/ouro-2.6b-pp6-zero3-1chip": (
        612438017, 612438017, 16108191768.0, 19329024024),
    "cell/mistral-7b-zero3-1chip": (
        698372096, 698372096, 4492247040.0, 4995538944),
    "cell/mistral-7b-zero3-4chip": (
        1570820096, 1570820096, 10330963968.0, 11840839680),
    "mistral/7b": (7241732096, 7241732096, 48282624000.0, 56335294464),
    "mistral/tiny": (139584, 139584, 880704.0, 1034112),
    "mixtral/8x7b": (46702792704, 12879925248, 80501563392.0, 83722002432),
    "mixtral/tiny": (287552, 189248, 1234560.0, 1332096),
    "qwen2_moe/a2.7b": (13692930048, 2066319360, 14814130176.0,
                        17229754368),
    "qwen2_moe/tiny": (337088, 238784, 1531776.0, 1629312),
    "gpt2/tiny": (141056, 141056, 945408.0, 1042944),
    "llama/tiny": (139584, 139584, 936576.0, 1034112),
    "falcon/tiny": (119168, 119168, 814080.0, 911616),
    "phi/tiny": (165376, 165376, 1091328.0, 1188864),
    "gptneox/tiny": (165632, 165632, 1092864.0, 1190400),
    "bloom/tiny": (132992, 132992, 897024.0, 994560),
    "gptj/tiny": (164864, 164864, 1088256.0, 1185792),
    "opt/tiny": (141056, 141056, 945408.0, 1042944),
    "qwen/tiny": (139840, 139840, 938112.0, 1035648),
    "internlm/tiny": (148288, 148288, 988800.0, 1086336),
}


def _counted_config(case):
    family, size = case.split("/")
    if family != "cell":
        return get_model_class(family)(size=size).config
    import json
    import pathlib
    file = json.loads((pathlib.Path(__file__).parent.parent / "benchmark"
                       / "configs" / f"{size}.json").read_text())
    program = file["program"]
    return get_model_class(program["model_type"])(
        size=program["preset"], **program.get("model_overrides", {}),
        num_layers=file["num_hidden_layers"],
        max_seq_len=file["max_position_embeddings"]).config


@pytest.mark.parametrize("case", PARENT_COUNTS)
def test_counts_are_the_parents(case):
    c = _counted_config(case)
    params, active, causal, full = PARENT_COUNTS[case]
    assert c.num_params() == params
    assert c.num_active_params() == active
    s = c.max_seq_len
    assert c.flops_per_token(s) == pytest.approx(causal, rel=1e-12, abs=0)
    assert c.flops_per_token(s, causal=False) == pytest.approx(
        full, rel=1e-12, abs=0)


@pytest.mark.parametrize("family,stranger", [
    ("kimi_linear", dict(mamba_n_heads=4)),
    ("granite_hybrid", dict(kda_head_groups=4)),
    ("mellum", dict(kda_head_groups=4)),
    ("qwen3_next", dict(kda_head_groups=4)),
    ("lfm2_moe", dict(kda_head_groups=4)),
    ("xing4_0", dict(qk_norm_init=2.0)),
    ("kimi_linear", dict(hc_mult=4)),
    ("laguna", dict(hc_mult=4)),
    ("deepseek_v3", dict(hc_mult=4)),
    ("deepseek_v3", dict(mla_use_nope=True)),
    ("nemotron_h", dict(mamba_n_heads=4)),
    ("granite_hybrid", dict(mamba_num_heads=4)),
    ("mellum", dict(num_attention_heads_per_layer=[4, 4, 4, 4])),
    ("granite_hybrid", dict(conv_L_cache=3)),
    ("kimi_linear", dict(qk_norm_init=2.0)),
    ("ouro", dict(layer_types=["attention", "attention"])),
    ("mistral", dict(total_ut_steps=4)),
    ("mixtral", dict(moe_held_experts=2)),
])
def test_another_familys_field_is_refused(family, stranger):
    """An override that is no field of the family's own config class is a
    ``TypeError`` at construction, not a model built in silence."""
    with pytest.raises(TypeError, match=next(iter(stranger))):
        get_model_class(family)(size="tiny", **stranger)


def test_the_base_config_names_no_family():
    """``models/base.py`` is what the one-kind decoder reads: a family's
    fields and arithmetic live in the family's file (``models/stack.py``
    for what the stacks of kinds share), comments included."""
    import dataclasses
    import inspect

    from deepspeed_tpu.models import base
    source = inspect.getsource(base).lower()
    for word in ("kda", "mamba", "mla", "layer_types", "rope_parameters",
                 "total_ut_steps", "kimi", "granite", "mellum", "ouro"):
        assert word not in source, word
    fields = {f.name for f in dataclasses.fields(base.ModelConfig)}
    assert "moe_held_experts" not in fields and "exit_gate" not in fields
    for gone in ("_stack_params", "_hybrid_params", "_window_stack_params",
                 "layer_kinds", "window_stack"):
        assert not hasattr(base.ModelConfig, gone), gone
