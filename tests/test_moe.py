import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import Mixtral
from deepspeed_tpu.moe import MoE, moe_ffn, top_k_gating


def test_top_k_gating_shapes_and_capacity():
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    combine, dispatch, aux, metrics = top_k_gating(
        logits, k=2, capacity_factor=1.0)
    n, e, c = combine.shape
    assert (n, e) == (64, 8)
    assert metrics["capacity"] == c == 16  # 64*2/8 * 1.0
    # each token contributes weight <= 1 and uses <= k slots
    assert float(jnp.max(jnp.sum(combine, axis=(1, 2)))) <= 1.0 + 1e-5
    assert int(jnp.max(jnp.sum(dispatch, axis=(1, 2)))) <= 2
    # no capacity slot is double-booked
    assert int(jnp.max(jnp.sum(dispatch, axis=0))) <= 1
    assert float(aux) > 0


def test_gating_routes_to_top_expert():
    # strongly peaked logits -> every token goes to its argmax expert
    logits = jnp.full((8, 4), -10.0)
    pick = jnp.arange(8) % 4
    logits = logits.at[jnp.arange(8), pick].set(10.0)
    combine, dispatch, _, metrics = top_k_gating(
        logits, k=1, capacity_factor=2.0)
    got = jnp.argmax(jnp.sum(combine, axis=-1), axis=-1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(pick))
    assert float(metrics["drop_fraction"]) == 0.0


def test_capacity_drop():
    # all tokens want expert 0; capacity forces drops
    logits = jnp.zeros((32, 4)).at[:, 0].set(10.0)
    combine, dispatch, _, metrics = top_k_gating(
        logits, k=1, capacity_factor=1.0, min_capacity=4)
    assert float(metrics["drop_fraction"]) > 0.5


def test_moe_module_forward():
    moe = MoE(hidden_size=32, ffn_dim=64, num_experts=4, k=2,
              capacity_factor=2.0)
    params = moe.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    y, aux = moe(params, x)
    assert y.shape == x.shape
    assert jnp.isfinite(y).all() and float(aux) > 0


def test_pr_moe_residual():
    moe = MoE(hidden_size=16, ffn_dim=32, num_experts=2, k=1,
              use_residual=True, capacity_factor=2.0)
    params = moe.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 16))
    y, _ = moe(params, x)
    assert y.shape == x.shape


def test_mixtral_forward_and_loss():
    model = Mixtral(size="tiny")
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 512)
    logits, aux = model.apply(params, tokens, return_aux=True)
    assert logits.shape == (2, 32, 512)
    assert float(aux) > 0  # router aux accumulated over layers
    loss = model.loss(params, (tokens[:, :-1], tokens[:, 1:]))
    assert jnp.isfinite(loss)


def test_mixtral_param_count():
    model = Mixtral(size="tiny")
    params = model.init(jax.random.PRNGKey(0))
    actual = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert actual == model.config.num_params()


def test_mixtral_ep_parity(devices8):
    """BASELINE config 5 analogue: EP+ZeRO-3 training must match the
    single-axis run (expert parallelism only relocates experts)."""
    def cfg(ep):
        return {
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 3},
            "mesh": {"ep": ep, "fsdp": -1},
            "steps_per_print": 100,
        }
    tokens = jax.random.randint(jax.random.PRNGKey(2), (8, 33), 0, 512)
    batch = (tokens[:, :-1], tokens[:, 1:])

    e1, _, _, _ = ds.initialize(model=Mixtral(size="tiny"), config=cfg(1))
    l1 = [float(e1.train_batch(batch)) for _ in range(2)]
    e4, _, _, _ = ds.initialize(model=Mixtral(size="tiny"), config=cfg(4))
    l4 = [float(e4.train_batch(batch)) for _ in range(2)]
    np.testing.assert_allclose(l4, l1, rtol=2e-4, atol=2e-4)
    # experts really are sharded over ep
    wq = e4.state["params"]["layers"]["experts"]["w_up"]
    assert "ep" in str(wq.sharding.spec)


def test_moe_grouped_dispatch_exact_topk(devices8):
    """Serving dispatch (moe_ffn_grouped; reference: inference/v2
    cutlass_ops moe_gemm + moe_gather/moe_scatter): sort-by-expert +
    ragged_dot must equal brute-force exact top-k routing — no capacity
    padding, no drops."""
    from deepspeed_tpu.moe.sharded_moe import moe_ffn_grouped
    key = jax.random.PRNGKey(0)
    B, S, D, F, E, K = 2, 8, 16, 32, 4, 2
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, D))
    gate_w = jax.random.normal(ks[1], (D, E)) * 0.1
    experts = {"w_gate": jax.random.normal(ks[2], (E, D, F)) * 0.1,
               "w_up": jax.random.normal(ks[3], (E, D, F)) * 0.1,
               "w_down": jax.random.normal(ks[4], (E, F, D)) * 0.1}
    out, aux = jax.jit(
        lambda x: moe_ffn_grouped(x, gate_w, experts, k=K))(x)
    xt = np.asarray(x).reshape(-1, D)
    probs = np.asarray(jax.nn.softmax(
        jnp.asarray(xt @ np.asarray(gate_w)), axis=-1))
    ref = np.zeros_like(xt)
    for n in range(xt.shape[0]):
        idx = np.argsort(-probs[n])[:K]
        w = probs[n][idx]
        w = w / w.sum()
        for e_i, wi in zip(idx, w):
            gg = xt[n] @ np.asarray(experts["w_gate"][e_i])
            uu = xt[n] @ np.asarray(experts["w_up"][e_i])
            h = (gg / (1 + np.exp(-gg))) * uu
            ref[n] += wi * (h @ np.asarray(experts["w_down"][e_i]))
    np.testing.assert_allclose(np.asarray(out).reshape(-1, D), ref,
                               rtol=1e-4, atol=1e-5)
    assert np.isfinite(float(aux))


def test_moe_serving_dispatch_wired(devices8):
    """moe_grouped_dispatch=True flips the MoE model onto the grouped
    dispatch and generation still runs; a later ds.initialize resets
    the flag so training keeps the capacity einsum (grouped is opt-in:
    ragged_dot measured slower than the einsum on v5e decode)."""
    import deepspeed_tpu as ds_
    model = Mixtral(size="tiny", max_seq_len=64)
    assert model.moe_serving_dispatch is False
    eng = ds_.init_inference(model, dtype="float32", max_out_tokens=48)
    assert eng.module.moe_serving_dispatch is False  # opt-in, not default
    eng = ds_.init_inference(model, dtype="float32", max_out_tokens=48,
                             moe_grouped_dispatch=True)
    # the flag binds to the engine's own shallow copy; the shared model
    # instance is never mutated (ADVICE r4)
    assert eng.module.moe_serving_dispatch is True
    assert model.moe_serving_dispatch is False
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 8), 0, 512)
    out = eng.generate(toks, max_new_tokens=4)
    assert out.shape == (2, 12)
    # training keeps the capacity einsum on the shared instance
    ds_.initialize(model=model, config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0}, "steps_per_print": 10 ** 9})
    assert model.moe_serving_dispatch is False


def test_moe_quantized_experts_serving(devices8):
    """Weight-only int8 expert quantization (reference: inference/v2
    cutlass mixed_gemm / ZeRO-Inference weight quant): quantized
    generate must run and track the bf16 logits closely."""
    import deepspeed_tpu as ds_
    model = Mixtral(size="tiny", max_seq_len=64)
    params = model.init(jax.random.PRNGKey(3))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 512)
    e_ref = ds_.init_inference(model, dtype="float32",
                               max_out_tokens=48, params=params)
    ref_logits = e_ref.forward(toks)
    e_q = ds_.init_inference(model, dtype="float32", max_out_tokens=48,
                             quantize_moe_experts=True, params=params)
    q = e_q.params["layers"]["experts"]
    assert q["w_up_q"].dtype == jnp.int8 and "w_up" not in q
    q_logits = e_q.forward(toks)
    # int8 weight error is small relative to logit scale
    denom = float(jnp.max(jnp.abs(ref_logits))) or 1.0
    rel = float(jnp.max(jnp.abs(q_logits - ref_logits))) / denom
    assert rel < 0.05, rel
    out = e_q.generate(toks, max_new_tokens=4)
    assert out.shape == (2, 12)


# ---- ISSUE 16: ep-sharded dispatch + no-drop gating + dispatch wire --


def test_no_drop_gating_conserves_tokens():
    """Satellite regression: drop_tokens=False must size capacity to
    the worst-case expert load even at capacity_factor 0 — the old
    code still applied the factor and silently dropped overflow."""
    # adversarial load: every token wants expert 0
    logits = jnp.zeros((32, 4)).at[:, 0].set(10.0)
    combine, dispatch, _, metrics = top_k_gating(
        logits, k=1, capacity_factor=0.0, drop_tokens=False)
    assert dispatch.shape[2] >= 32          # capacity >= n (worst case)
    assert int(jnp.sum(dispatch)) == 32     # every token kept
    assert float(metrics["drop_fraction"]) == 0.0
    # every token's full gate weight survives (nothing zeroed by keep)
    sums = np.asarray(jnp.sum(combine, axis=(1, 2)))
    np.testing.assert_allclose(sums, sums[0] * np.ones(32), rtol=1e-6)
    assert sums[0] > 0.99  # softmax top-1 of a +10 logit margin


def test_dequantize_experts_gateless_roundtrip():
    """Satellite regression: dequantize_experts keyed off the literal
    'w_up_q'; any *_q key must mark the quantized form so gate-less
    (gelu-only) expert dicts round-trip too."""
    from deepspeed_tpu.moe.sharded_moe import (dequantize_experts,
                                               quantize_experts)
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    experts = {"w_up": jax.random.normal(ks[0], (4, 16, 32)) * 0.1,
               "w_down": jax.random.normal(ks[1], (4, 32, 16)) * 0.1}
    q = quantize_experts(experts)
    assert "w_up_q" in q and "w_up" not in q
    deq = dequantize_experts(q, jnp.float32)
    assert set(deq) == {"w_up", "w_down"}
    for k in experts:
        np.testing.assert_allclose(np.asarray(deq[k]),
                                   np.asarray(experts[k]), atol=2e-3)
    # an unquantized (plain float) dict passes through untouched
    assert dequantize_experts(experts, jnp.float32) is experts


def _rand_moe_inputs(key, b=2, s=16, d=32, e=4, f=64):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, d))
    gate_w = jax.random.normal(ks[1], (d, e)) * 0.1
    experts = {"w_gate": jax.random.normal(ks[2], (e, d, f)) * 0.1,
               "w_up": jax.random.normal(ks[3], (e, d, f)) * 0.1,
               "w_down": jax.random.normal(ks[4], (e, f, d)) * 0.1}
    return x, gate_w, experts


def test_moe_ffn_matches_grouped_at_zero_drop():
    """moe_ffn with no-drop capacity (drop_tokens=False) and
    moe_ffn_grouped both implement exact top-k routing — the capacity
    einsum and the sort-by-expert ragged GEMM must agree."""
    from deepspeed_tpu.moe.sharded_moe import moe_ffn_grouped
    x, gate_w, experts = _rand_moe_inputs(jax.random.PRNGKey(7))
    ref, _ = moe_ffn(x, gate_w, experts, k=2, capacity_factor=0.0,
                     drop_tokens=False, activation="swiglu")
    got, _ = moe_ffn_grouped(x, gate_w, experts, k=2,
                             activation="swiglu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_quantized_experts_error_bound():
    """Weight-only int8 experts through the full routed FFN: the output
    error stays within the per-channel quantization bound."""
    from deepspeed_tpu.moe.sharded_moe import (dequantize_experts,
                                               quantize_experts)
    x, gate_w, experts = _rand_moe_inputs(jax.random.PRNGKey(11))
    ref, _ = moe_ffn(x, gate_w, experts, k=2, capacity_factor=0.0,
                     drop_tokens=False, activation="swiglu")
    deq = dequantize_experts(quantize_experts(experts), x.dtype)
    got, _ = moe_ffn(x, gate_w, deq, k=2, capacity_factor=0.0,
                     drop_tokens=False, activation="swiglu")
    denom = float(jnp.max(jnp.abs(ref))) or 1.0
    assert float(jnp.max(jnp.abs(got - ref))) / denom < 0.05


def test_moe_step_contextvar():
    """The step seed the quantized dispatch wire consumes: bound inside
    the engine's micro_loss, uint32 zeros when unbound (eval traces)."""
    from deepspeed_tpu.moe.dispatch import current_step, moe_step
    s = current_step()
    assert s.dtype == jnp.uint32 and int(s) == 0
    with moe_step(5):
        assert int(current_step()) == 5
    assert int(current_step()) == 0


def test_dispatcher_unsupported_reason():
    from deepspeed_tpu.moe.dispatch import dispatcher_unsupported_reason
    from deepspeed_tpu.parallel.mesh import MeshTopology, TopologyConfig
    topo = MeshTopology(TopologyConfig())
    assert dispatcher_unsupported_reason(topo, 4) is None
    # ep must divide the expert count
    n = len(jax.devices())
    if n >= 2:
        topo2 = MeshTopology(TopologyConfig(ep=2))
        assert dispatcher_unsupported_reason(topo2, 3) is not None
        assert dispatcher_unsupported_reason(topo2, 4) is None


def test_ep_sharded_dispatch_sum_parity(devices8):
    """The ep-sharded explicit dispatch/combine exchange must reproduce
    the single-device capacity einsum: the reduce-scatter of per-shard
    partial dispatch tables is a SUM, so fp32 parity is exact up to
    reduction order; the int8 stochastic wire tracks within the
    quantization bound."""
    from deepspeed_tpu.moe.dispatch import EpShardedDispatcher, moe_step
    from deepspeed_tpu.parallel.mesh import MeshTopology, TopologyConfig
    topo = MeshTopology(TopologyConfig(fsdp=2, zps=2, ep=2))
    x, gate_w, experts = _rand_moe_inputs(jax.random.PRNGKey(3), b=4)
    ref, aux_ref = moe_ffn(x, gate_w, experts, k=2, capacity_factor=0.0,
                           drop_tokens=False, activation="swiglu")
    disp = EpShardedDispatcher.for_topology(topo)
    assert disp.slow_axes == ("fsdp",) and disp.fast_axes == ("zps",)
    # jitted, as the engine runs it: eager shard_map dispatches op by op
    with topo.mesh:
        out, aux = jax.jit(lambda x, gate_w, experts: moe_ffn(
            x, gate_w, experts, k=2, capacity_factor=0.0,
            drop_tokens=False, activation="swiglu",
            dispatcher=disp))(x, gate_w, experts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-6)

    # int8 stochastic-rounded wire: gradients flow (straight-through),
    # forward tracks the fp32 exchange within the quantization bound
    disp8 = EpShardedDispatcher.for_topology(topo, wire_dtype="int8")

    def loss(xx):
        with topo.mesh:
            o, _ = moe_ffn(xx, gate_w, experts, k=2, capacity_factor=0.0,
                           drop_tokens=False, activation="swiglu",
                           dispatcher=disp8)
        return jnp.sum(o * o), o

    with moe_step(3):
        (v, o8), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(x)
    assert bool(jnp.all(jnp.isfinite(g)))
    denom = float(jnp.max(jnp.abs(ref))) or 1.0
    assert float(jnp.max(jnp.abs(o8 - ref))) / denom < 0.05
    ref_v = float(jnp.sum(ref * ref))
    assert abs(float(v) - ref_v) / abs(ref_v) < 1e-2


def test_engine_int8_dispatch_wire_meshsan(devices8):
    """Engine-backed acceptance (slow tier): int8 dispatch wire on an
    ep x zps x fsdp mesh trains under the meshsan traffic contract in
    raise mode, the router-telemetry gauges publish, and the loss
    tracks the fp32-wire engine within 1e-2."""

    def cfg(wire):
        return {"train_batch_size": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3},
                "mesh": {"fsdp": -1, "zps": 2, "ep": 2},
                "moe": {"wire_dtype": wire, "router_telemetry": True},
                "telemetry": {"enabled": True,
                              "executable_ledger": True},
                "meshsan": {"enabled": True, "mode": "raise"},
                "steps_per_print": 10 ** 9}

    tokens = jax.random.randint(jax.random.PRNGKey(2), (8, 33), 0, 512)
    batch = (tokens[:, :-1], tokens[:, 1:])
    losses = {}
    for wire in ("fp32", "int8"):
        eng, _, _, _ = ds.initialize(model=Mixtral(size="tiny"),
                                     config=cfg(wire))
        assert eng._moe_dispatcher is not None
        assert eng._moe_dispatcher.wire_dtype == wire
        losses[wire] = [float(eng.train_batch(batch)) for _ in range(2)]
        from deepspeed_tpu.telemetry.registry import get_registry
        reg = get_registry()
        assert reg is not None
        snap = reg.snapshot()
        assert "ds_moe_router_drop_fraction" in snap
        assert "ds_moe_router_capacity" in snap
    rel = max(abs(a - b) / abs(b)
              for a, b in zip(losses["int8"], losses["fp32"]))
    assert rel < 1e-2, (losses, rel)
