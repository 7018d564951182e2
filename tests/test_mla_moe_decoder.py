"""The latent-attention, routed-expert decoder at a small size on the
CPU, on both residual paths the builder makes: hyper-connected streams
with every expert held (``CFG``) and the plain path with a share of a
wider router held (``SHARE``).  Every new layer against the plain
reference (``benchmark/reference/mla_moe_decoder.py`` and, for the
plain path, ``mla_moe_plain.py``, which share no code with the
package's layers), the served path (chunked prefill, fork, token
generation through ``InferenceEngine`` sessions) against the
reference's full forward, and planted faults that the comparison has to
catch."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mla_moe_decoder as ref
from benchmark.reference import mla_moe_plain as plain
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.models.mla_moe_decoder import from_config
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.conf.computation_graph import (
    ComputationGraphConfiguration, StreamExpandVertex, StreamSumVertex)
from deeplearning4j_tpu.nn.layers import decoder
from deeplearning4j_tpu.ops.attention import latent_ring_attention_dense
from deeplearning4j_tpu.serving import InferenceEngine
from deeplearning4j_tpu.serving.sessions import SessionError

CFG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, intermediate_size=160,
    moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
    n_shared_experts=1, routed_scaling_factor=2.0, norm_topk_prob=True,
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64,
                  "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"},
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
#: alpha, b, phi and the selection bias of order one, so that every
#: dynamic path (per-token mixing, biased selection) matters
ORDER_ONE = dict(cache_len=32, init_std=0.1, hc_alpha_init=0.5,
                 hc_bias_std=1.0, router_bias_std=0.2, seed=3)
#: the plain path with a share, the keys of ``benchmark/configs/ax_k1.json``:
#: no ``hc_*``; the file's ``n_routed_experts`` are the 6 held here (the
#: second of four chips), ``published`` gives the router's 24; 3 a token
HELD = [6, 7, 8, 9, 10, 11]
SHARE = {**{k: v for k, v in CFG.items()
            if not k.startswith(("hc_", "mhc_"))},
         "n_routed_experts": 6, "num_experts_per_tok": 3,
         "routed_scaling_factor": 2.5,
         "rope_scaling": {**CFG["rope_scaling"], "factor": 32},
         "n_group": 8, "topk_group": 4, "topk_method": "none",
         "published": {"n_routed_experts": 24},
         "builder_args": {"experts_held": HELD}}
SHARE_ARGS = dict(cache_len=32, init_std=0.1, seed=3, experts_held=HELD)
#: configuration, builder arguments and reference, by residual path
KINDS = {"streams": (CFG, ORDER_ONE, ref), "plain": (SHARE, SHARE_ARGS, plain)}
#: bf16 against the float32 reference at this size, the median over the
#: positions of a position's relative error: 0.011-0.013 measured over
#: five seeds (8 significant bits, ~40 roundings between ids and
#: logits); four times that.  A position at which rounding flipped a
#: routing choice between two nearly tied experts reads 0.2-0.9 whatever
#: the precision (one such position in 60 is most seeds' lot), so all
#: positions together say little; the benchmark's comparison has the
#: same two numbers.  Weights rounded to float8 read 0.20 (0.099 on the
#: plain path).
BF16_BOUND = 0.05


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def median_rel(got, want):
    """The median over (row, position) of a position's ``rel``."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.median([rel(g, w) for g, w in zip(
        got.reshape(-1, got.shape[-1]), want.reshape(-1, want.shape[-1]))]))


def build(dtype=None, for_inference=False, kind="streams", cfg=None, **kw):
    cfg, args = cfg or KINDS[kind][0], KINDS[kind][1]
    return ComputationGraph(from_config(
        cfg, dtype=dtype, **{**args, **kw})).init(
            for_inference=for_inference)


@pytest.fixture(scope="module")
def net():
    return build()


@pytest.fixture(scope="module")
def nets(net):
    """``kind -> (configuration, float32 net, reference)``."""
    return {"streams": (CFG, net, ref),
            "plain": (SHARE, build(kind="plain"), plain)}


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(0).randint(
        0, CFG["vocab_size"], (3, 20)).astype(np.int32)


def acts(shape, seed=1):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.float32)


# ------------------------------------------------ layers vs the reference
def _attention(net):
    layer, p = net.vertices["L1_attn"].layer, net.params["L1_attn"]
    x = acts((2, 9, 64))
    return layer.forward(p, {}, x, train=False)[0], ref.attention(CFG, p, x)


def _moe(net):
    layer, p = net.vertices["L1_moe"].layer, net.params["L1_moe"]
    x = acts((2, 9, 64))
    return (layer.forward(p, layer.init_state(), x, train=False)[0],
            ref.moe(CFG, p, x))


def _ffn(net):
    layer, p = net.vertices["L0_ffn"].layer, net.params["L0_ffn"]
    x = acts((2, 9, 64))
    return layer.forward(p, {}, x, train=False)[0], ref.dense_ffn(CFG, p, x)


def _norm(net):
    layer = net.vertices["final_norm"].layer
    p = {"gain": acts((64,), 5)}
    x = acts((2, 9, 64))
    return (layer.forward(p, {}, x, train=False)[0],
            ref.rmsnorm(x, CFG["rms_norm_eps"], p["gain"]))


def _read(net):
    layer, p = net.vertices["L1_ffn_read"].layer, net.params["L1_ffn_read"]
    x = acts((2, 9, 4, 64))
    return layer.forward(p, {}, x, train=False)[0], ref.stream_read(CFG, p, x)


def _write(net):
    layer = net.vertices["L1_ffn_write"].layer
    p = net.params["L1_ffn_write"]
    x, y = acts((2, 9, 4, 64)), acts((2, 9, 64), 2)
    return (layer.forward(p, {}, (x, y), train=False)[0],
            ref.stream_write(CFG, p, x, y))


def _rotary(net):
    layer = net.vertices["L0_attn"].layer
    x, pos = acts((2, 9, 4, 8)), jnp.arange(100, 109)
    inv_freq, factor = decoder.yarn_inv_freq(
        layer.d_rope, layer.rope_theta, layer.rope_scaling)
    return (decoder.rotate(x, pos, inv_freq, factor),
            ref.rotary(x, *ref.rotary_tables(CFG, pos)))


@pytest.mark.parametrize("pair", [_attention, _moe, _ffn, _norm, _read,
                                  _write, _rotary],
                         ids=lambda f: f.__name__.strip("_"))
def test_layer_agrees_with_the_reference(net, pair):
    """The attention case is also absorbed (the program) against
    decompressed (the reference) attention."""
    with jax.default_matmul_precision("highest"):
        got, want = pair(net)
    assert got.shape == want.shape
    assert rel(got, want) < 1e-5


def test_yarn_blends_interpolated_and_extrapolated_frequencies():
    inv_freq, factor = decoder.yarn_inv_freq(8, 10000.0,
                                             CFG["rope_scaling"])
    plain, one = decoder.yarn_inv_freq(8, 10000.0, None)
    assert factor == 1.0 and one == 1.0       # mscale / mscale_all_dim
    assert np.all(np.asarray(inv_freq) <= np.asarray(plain) * (1 + 1e-6))
    assert np.all(np.asarray(inv_freq) >= np.asarray(plain) / 64 * (1 - 1e-6))
    layer = build().vertices["L0_attn"].layer
    m = 0.1 * np.log(64) + 1
    assert layer.sm_scale() == pytest.approx(24 ** -0.5 * m * m)


def test_h_res_is_doubly_stochastic(net):
    layer = net.vertices["L2_attn_write"].layer
    h_post, h_res = layer.mixing(net.params["L2_attn_write"],
                                 acts((2, 7, 4, 64)))
    np.testing.assert_allclose(np.asarray(h_res).sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_res).sum(-2), 1.0, atol=1e-4)
    assert np.asarray(h_res).std() > 0.05          # and not uniform
    assert ((np.asarray(h_post) > 0) & (np.asarray(h_post) < 2)).all()


def test_the_carry_holds_a_latent_row_a_token_and_nothing_per_head(net):
    carries = net._init_carries(3, cache_len=16)
    assert sorted(carries) == ["L0_attn", "L1_attn", "L2_attn"]
    c_ring, r_ring, cursor = carries["L1_attn"]
    assert c_ring.shape == (3, 16, CFG["kv_lora_rank"])
    assert r_ring.shape == (3, 16, CFG["qk_rope_head_dim"])
    assert cursor.shape == () and cursor.dtype == jnp.int32
    per_token = sum(a.size for a in (c_ring, r_ring)) // (3 * 16)
    assert per_token == CFG["kv_lora_rank"] + CFG["qk_rope_head_dim"]
    grown = net.vertices["L1_attn"].layer.grow_carry(carries["L1_attn"], 32)
    assert grown[0].shape == (3, 32, 32) and grown[1].shape == (3, 32, 8)


def test_the_shares_of_all_holders_add_up_to_the_whole_layer(net):
    """Four chips of two experts each: the routed parts they compute,
    plus the shared expert counted once, are the whole layer's output;
    and the reference, given a share, gives that share."""
    whole, p = net.vertices["L1_moe"].layer, net.params["L1_moe"]
    x = acts((2, 9, 64))
    full = whole.forward(p, whole.init_state(), x, train=False)[0]
    f = CFG["moe_intermediate_size"]
    shared_only = decoder._gated(x, p["Sg"], p["Su"], p["Sd"])
    routed = 0.0
    for held in ([0, 1], [2, 3], [4, 5], [6, 7]):
        cols = np.concatenate([np.arange(e * f, (e + 1) * f) for e in held])
        share = dict(p, Wg=p["Wg"][:, cols], Wu=p["Wu"][:, cols],
                     Wd=p["Wd"][cols])
        layer = decoder.MixtureOfExperts(**{
            **{k: getattr(whole, k) for k in (
                "n_in", "n_out", "n_experts", "top_k", "width", "n_shared",
                "routed_scaling", "norm_topk")}, "experts_held": held})
        part, state = layer.forward(share, layer.init_state(), x,
                                    train=False)
        assert rel(part, ref.moe(CFG, share, x, experts_held=held)) < 1e-5
        assert int(state["expert_tokens"].sum()) == 2 * 9 * 2
        routed = routed + (part - shared_only)
    assert rel(routed + shared_only, full) < 1e-5


def test_a_share_draws_the_experts_the_whole_layer_has():
    whole = build().params["L1_moe"]
    share = build(experts_held=[5, 2]).params["L1_moe"]
    f = CFG["moe_intermediate_size"]
    np.testing.assert_array_equal(share["Wg"][:, :f],
                                  whole["Wg"][:, 5 * f:6 * f])
    np.testing.assert_array_equal(share["Wd"][f:], whole["Wd"][2 * f:3 * f])
    assert share["router"].shape == (64, 8)


def test_every_share_of_a_wide_router_adds_up_to_the_uncut_layer(nets):
    """The plain path's toy: four chips of six of the router's 24
    experts.  Each chip's layer, built as the file builds it, draws its
    own experts; the routed parts they compute, plus the shared expert
    counted once, are what the UNCUT reference gives for the layer that
    holds all 24."""
    cfg, net, _ = nets["plain"]
    uncut = build(kind="plain", experts_held=None,
                  cfg={**SHARE, "n_routed_experts": 24, "published": {}})
    p_whole = uncut.params["L1_moe"]
    assert p_whole["Wg"].shape == (64, 24 * 32)
    x = acts((2, 9, 64))
    want = plain.moe({**SHARE, "n_routed_experts": 24}, p_whole, x)
    shared_only = decoder._gated(x, p_whole["Sg"], p_whole["Su"],
                                 p_whole["Sd"])
    routed = 0.0
    for chip in range(4):
        held = list(range(6 * chip, 6 * chip + 6))
        share = build(kind="plain", experts_held=held)
        layer, p = share.vertices["L1_moe"].layer, share.params["L1_moe"]
        assert layer.n_experts == 24 and layer.held() == held
        assert p["router"].shape == (64, 24) and p["Wg"].shape == (64, 192)
        np.testing.assert_array_equal(
            p["Wd"], p_whole["Wd"][192 * chip:192 * (chip + 1)])
        part, state = layer.forward(p, layer.init_state(), x, train=False)
        assert rel(part, plain.moe(SHARE, p, x, experts_held=held)) < 1e-5
        assert state["expert_tokens"].shape == (24,)
        assert int(state["expert_tokens"].sum()) == 2 * 9 * 3
        routed = routed + (part - shared_only)
    assert rel(routed + shared_only, want) < 1e-5
    # and the net under test is the second chip's
    np.testing.assert_array_equal(net.params["L1_moe"]["Wg"],
                                  p_whole["Wg"][:, 192:384])


def test_drawing_a_share_never_makes_the_whole_layer():
    """Every value ``init_params`` computes for 6 held of 1,536 experts
    (the jaxpr's equations, sub-jaxprs included) is smaller than a
    twentieth of one whole-layer matrix: a share is drawn expert by
    expert, each from a key of its own."""
    layer = decoder.MixtureOfExperts(
        n_in=64, n_out=64, n_experts=1536, top_k=3, width=32,
        experts_held=HELD, weight_init="distribution",
        dist=decoder.Distribution(kind="normal", std=0.1))
    whole = 64 * 1536 * 32

    def sizes(jaxpr):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                yield int(np.prod(var.aval.shape, dtype=np.int64))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    traced = jax.make_jaxpr(lambda k: layer.init_params(k, jnp.bfloat16))(
        jax.random.PRNGKey(0))
    largest = max(sizes(traced.jaxpr))
    assert largest == 64 * 1536            # the router, 1,536 wide
    shapes = jax.eval_shape(lambda k: layer.init_params(k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    assert shapes["Wg"].shape == (64, 6 * 32) and largest < whole // 20


# -------------------------------------------------------- the whole model
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_output_agrees_with_the_reference_and_json_round_trips(nets, ids,
                                                               kind):
    cfg, net, reference = nets[kind]
    want = reference.forward(cfg, net.params, ids)
    assert rel(net.output(ids), want) < 1e-5
    conf = from_config(cfg, **KINDS[kind][1])
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert json.loads(again.to_json()) == json.loads(conf.to_json())
    np.testing.assert_array_equal(
        ComputationGraph(again).init().output(ids), net.output(ids))


@pytest.mark.parametrize("kind,fault", [
    ("streams", "no_shared_expert"), ("streams", "no_routed_scaling"),
    ("streams", "rotary_off"), ("streams", "streams_as_one"),
    ("plain", "no_shared_expert"), ("plain", "no_routed_scaling"),
    ("plain", "rotary_off"), ("plain", "residual_off"),
    ("plain", "another_chips_share")])
def test_a_planted_fault_fails_the_comparison(nets, ids, kind, fault):
    """A program that left out the shared expert, the routed scaling
    factor or the rotary embedding, mixed its streams as one, dropped
    the residual add, or computed the picks of experts it does not hold
    (its matrices taken for the first chip's ids), reads as the
    reference with that fault planted reads against the sound one: far
    over any bound here."""
    cfg, net, reference = nets[kind]
    sound = reference.forward(cfg, net.params, ids)
    if fault == "another_chips_share":
        faulty = reference.forward(cfg, net.params, ids,
                                   experts_held=list(range(6)))
    else:
        faulty = reference.forward(cfg, net.params, ids, faults=(fault,))
    assert rel(faulty, sound) > 2 * BF16_BOUND


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_prefill_in_chunks_then_decode_agrees_with_the_full_forward(
        nets, ids, kind):
    cfg, net, reference = nets[kind]
    want = np.asarray(reference.forward(cfg, net.params, ids))
    with InferenceEngine(net, max_batch_size=4) as engine:
        assert engine.prefill_session("s", ids[:, :13], chunk=4,
                                      cache_len=32) == 13
        got = [engine.predict_session("s", ids[:, t:t + 1])
               for t in range(13, 20)]
        assert engine.sessions.session_position("s") == 20
        assert engine.sessions.session_capacity("s") == 32
    assert rel(np.stack(got, axis=1), want[:, 13:]) < 1e-5
    # and output(), from a zero ring, is the same path
    assert rel(net.output(ids), want) < 1e-5


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_bf16_serving_net_holds_two_bytes_a_parameter_and_stays_in_bound(
        ids, kind):
    cfg, _, reference = KINDS[kind]
    served = build("bfloat16", for_inference=True, kind=kind)
    leaves = jax.tree.leaves(served.params)
    assert {str(a.dtype) for a in leaves} == {"bfloat16"}
    assert all(state == {} for state in served.updater_state.values())
    n = sum(a.size for a in leaves)
    assert sum(a.nbytes for a in leaves) == 2 * n
    with pytest.raises(ValueError, match="for_inference"):
        served.fit(np.zeros((1, 4), np.int32), np.zeros((1, 4, 256)))
    want = np.asarray(reference.forward(cfg, served.params, ids))
    assert 1e-4 < median_rel(served.output(ids), want) < BF16_BOUND
    # the control: the same comparison with the matrices rounded one
    # step lower (float8) has to fail (0.20 with streams; 0.099 on the
    # plain path, whose residual carries the embedding on unrounded)
    low = reference.forward(cfg, served.params, ids, fp8_weights=True)
    assert median_rel(served.output(ids), low) > 1.5 * BF16_BOUND
    carries = served._init_carries(3, cache_len=32)
    assert carries["L0_attn"][0].dtype == jnp.bfloat16
    with InferenceEngine(served, max_batch_size=4) as engine:
        engine.prefill_session("s", ids[:, :-1], chunk=8, cache_len=32)
        out = engine.generate("s", ids[:, -1:], 4)
    kept = np.stack([np.asarray(k) for k in out.kept_logits], axis=1)
    sequence = np.concatenate([ids, out.ids[:, :-1]], axis=1)
    want = np.asarray(reference.forward(cfg, served.params, sequence,
                                        last=4))
    assert median_rel(kept, want[[0, 2]]) < BF16_BOUND


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_inference_init_draws_the_same_parameters(nets, kind):
    served = build(for_inference=True, kind=kind)
    for a, b in zip(jax.tree.leaves(nets[kind][1].params),
                    jax.tree.leaves(served.params)):
        np.testing.assert_array_equal(a, b)
    assert "_master" not in str(jax.tree.structure(served.updater_state))


# ----------------------------------------------------- sessions and engine
def _dispatches():
    return sum(monitor.counter(name, "").value(fn=fn)
               for name in ("jit_cache_hits_total", "jit_compiles_total")
               for fn in ("cg.token_step", "cg.decode_step",
                          "cg.fork_state", "cg.prefill_step"))


def test_generation_is_the_stepwise_argmax_at_one_dispatch_a_token(net, ids):
    with InferenceEngine(net, max_batch_size=4) as engine:
        engine.prefill_session("snap", ids[:, :-1], chunk=8, cache_len=32)
        engine.fork_session("snap", "a")
        engine.generate("a", ids[:, -1:], 2)           # compiles
        engine.fork_session("snap", "a")
        before, tokens = _dispatches(), monitor.counter(
            "serving_tokens_generated_total", "").value(model="default")
        out = engine.generate("a", ids[:, -1:], 6)
        assert _dispatches() - before == 6              # one a token
        assert monitor.counter("serving_tokens_generated_total", "").value(
            model="default") - tokens == 3 * 6
        engine.fork_session("snap", "b")
        step, stepwise = ids[:, -1:], []
        for _ in range(6):
            logits = engine.predict_session("b", step)
            step = np.argmax(logits, axis=-1).astype(np.int32)[:, None]
            stepwise.append(step)
    assert out.ids.shape == (3, 6) and out.ids.dtype == np.int32
    np.testing.assert_array_equal(out.ids, np.concatenate(stepwise, axis=1))
    sequence = np.concatenate([ids, out.ids[:, :-1]], axis=1)
    want = np.asarray(ref.forward(CFG, net.params, sequence, last=6))
    kept = np.stack([np.asarray(k) for k in out.kept_logits], axis=1)
    assert kept.shape == (2, 6, 256) and kept.dtype == np.float32
    assert rel(kept, want[[0, 2]]) < 1e-5              # rows 0 and B-1
    picks = out.expert_tokens
    assert sorted(picks) == ["L1_moe", "L2_moe"]
    assert all(int(row.sum()) == 3 * 6 * 2 for row in picks.values())
    spans = [s["name"] for s in monitor.tracer().events()]
    for name in ("serve/generate", "serve/decode_step", "serve/token_fetch",
                 "serve/fork", "serve/prefill_chunk"):
        assert name in spans, name


def test_two_forks_decode_alike_and_leave_the_snapshot_intact(net, ids):
    with InferenceEngine(net, max_batch_size=4) as engine:
        cache = engine.sessions
        engine.prefill_session("snap", ids[:, :-1], chunk=5, cache_len=32)
        held = jax.tree.map(np.asarray, cache.get_carries("snap"))
        engine.fork_session("snap", "a")
        a = engine.generate("a", ids[:, -1:], 5)
        engine.fork_session("snap", "b")
        b = engine.generate("b", ids[:, -1:], 5)
        np.testing.assert_array_equal(a.ids, b.ids)
        # the forks' steps donated their own rings, never the snapshot's
        for kept, now in zip(jax.tree.leaves(held), jax.tree.leaves(
                jax.tree.map(np.asarray, cache.get_carries("snap")))):
            np.testing.assert_array_equal(kept, now)
        assert cache.session_position("snap") == 19
        assert cache.session_position("a") == 19 + 5
        assert cache.session_version("a") == cache.session_version("snap")
        assert monitor.gauge("serving_session_state_bytes", "").value(
            model="default", kind="latent") == cache.state_bytes() > 0
        with pytest.raises(SessionError, match="no session"):
            engine.fork_session("nobody", "c")
        with pytest.raises(SessionError, match="do not fit"):
            engine.generate("a", a.ids[:, -1:], 32)


def test_a_second_served_net_asks_the_executable_store_for_its_programs(
        ids, tmp_path):
    """The three programs of generation say what they close over, so a
    store serves them to the next process (here: the next net of the
    same conf) as it serves ``init()`` and the fit step."""
    from deeplearning4j_tpu.monitor import jit_watch
    from deeplearning4j_tpu.serving.compile_cache import ExecutableStore

    def results(fn):
        values = monitor.snapshot().get(jit_watch.STORE_TOTAL, {}).get(
            "values", {})
        return {labels.split('result="')[1].rstrip('"}'): int(v)
                for labels, v in values.items() if f'fn="{fn}"' in labels}

    def generated():
        with InferenceEngine(build(for_inference=True),
                             max_batch_size=4) as engine:
            engine.prefill_session("snap", ids[:, :-1], chunk=19,
                                   cache_len=32)
            engine.fork_session("snap", "a")
            return engine.generate("a", ids[:, -1:], 4)

    programs = ("cg.token_step", "cg.prefill_step", "cg.fork_state")
    monitor.reset()
    try:
        jit_watch.set_executable_store(
            ExecutableStore(str(tmp_path / "executables")))
        first = generated()
        assert all(results(fn).get("miss_absent") == 1 for fn in programs)
        written = [fn for fn in programs if results(fn).get("written")]
        jit_watch.set_executable_store(
            ExecutableStore(str(tmp_path / "executables")))
        second = generated()
        # XLA:CPU cannot serialize every executable (a sort): what was
        # written is loaded, what was not is derived again
        for fn in programs:
            assert results(fn).get("hit", 0) == (fn in written), fn
        assert "cg.fork_state" in written
    finally:
        jit_watch.set_executable_store(None)
        monitor.reset()
    np.testing.assert_array_equal(first.ids, second.ids)
    for a, b in zip(first.kept_logits, second.kept_logits):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_token_ids_reach_the_embedding_as_integers():
    """Ids over 256 survive a bf16 net's session step: they are never
    cast to the engine's float dtype."""
    big = dict(CFG, vocab_size=1024)
    served = ComputationGraph(from_config(
        big, dtype="bfloat16", **ORDER_ONE)).init(for_inference=True)
    ids = np.array([[1001, 513, 770]], np.int32)
    with InferenceEngine(served, max_batch_size=1) as engine:
        got = engine.predict_session("s", ids[:, :, None])
    assert rel(got, served.output(ids)) < 1e-6


# ----------------------------------------------------- the builder's graphs
def test_the_builder_makes_the_graph_its_file_asks_for(nets):
    """With ``hc_mult`` the graph is what it was before the plain path
    existed (vertex names and parameter tree pinned here); without, one
    stream: a norm, the sublayer and an add, twice a layer."""
    streams, flat = nets["streams"][1], nets["plain"][1]
    assert list(streams.vertices)[:10] == [
        "embed", "streams", "L0_attn_read", "L0_attn_norm", "L0_attn",
        "L0_attn_write", "L0_ffn_read", "L0_ffn_norm", "L0_ffn",
        "L0_ffn_write"]
    assert list(streams.vertices)[-3:] == ["stream_sum", "final_norm", "head"]
    assert len(streams.vertices) == 2 + 3 * 8 + 3
    assert sorted(streams.params["L1_attn_write"]) == [
        "alpha_post", "alpha_res", "b_post", "b_res", "phi_post", "phi_res"]
    assert sum(a.size for a in jax.tree.leaves(streams.params)) == 268130
    assert list(flat.vertices) == ["embed"] + [
        f"L{i}_{part}" for i in range(3) for part in (
            "attn_norm", "attn", "attn_add", "ffn_norm",
            "ffn" if i == 0 else "moe", "ffn_add")] + ["final_norm", "head"]
    assert flat.vertices["L1_ffn_add"].inputs == ["L1_attn_add", "L1_moe"]
    assert flat.vertices["L0_attn_add"].inputs == ["embed", "L0_attn"]
    assert not any("stream" in n or "read" in n or "write" in n
                   for n in flat.vertices)
    layer = flat.vertices["L2_moe"].layer
    assert (layer.n_experts, layer.top_k, layer.held(),
            layer.router_bias_std) == (24, 3, HELD, 0.0)
    assert float(jnp.abs(flat.params["L2_moe"]["router_bias"]).max()) == 0.0


def test_a_shares_file_has_to_name_the_experts_it_holds():
    with pytest.raises(ValueError, match="6 of 24 routed experts"):
        from_config(SHARE, cache_len=32)
    with pytest.raises(ValueError, match="6 of 24 routed experts"):
        from_config(SHARE, cache_len=32, experts_held=[0, 1])
    # no published count: the file's own is the router's width
    assert from_config({**SHARE, "published": {}}, cache_len=32).vertices[
        "L1_moe"].layer.n_experts == 6


def test_a_share_counts_the_picks_that_named_its_experts(nets, ids):
    """``moe_held_picks_total{layer}`` grows by the picks of a call's
    steps that named a held expert (from the same int32 counts as
    ``moe_expert_tokens_total``), ``moe_experts_held{layer}`` says how
    many a layer holds, and the steps of this net are counted by form
    like any other's."""
    _, flat, _ = nets["plain"]

    def held_picks(layer):
        return monitor.counter("moe_held_picks_total", "").value(
            model="default", layer=layer)

    def steps(name, path, **labels):
        return monitor.counter(name, "").value(path=path, **labels)

    before = {v: held_picks(v) for v in ("L1_moe", "L2_moe")}
    launched = (steps("moe_experts_steps_total", "dense"),
                steps("latent_attention_steps_total", "dense",
                      weights="stored"))
    with InferenceEngine(flat, max_batch_size=4) as engine:
        engine.prefill_session("s", ids[:, :-1], chunk=8, cache_len=32)
        out = engine.generate("s", ids[:, -1:], 5)
    for vertex, row in out.expert_tokens.items():
        assert row.shape == (24,) and int(row.sum()) == 3 * 5 * 3
        on_held = int(row[HELD].sum())
        assert 0 < on_held < int(row.sum())
        assert held_picks(vertex) - before[vertex] == on_held
        assert monitor.gauge("moe_experts_held", "").value(
            model="default", layer=vertex) == 6
    # three prefill chunks (8, 8 and 3 tokens a row) and five token steps
    assert steps("moe_experts_steps_total", "dense") - launched[0] == 8
    # a net not prepared for serving lays its weights inside the step
    assert steps("latent_attention_steps_total", "dense",
                 weights="stored") - launched[1] == 5
    # a net that holds every expert counts every pick
    _, whole, _ = nets["streams"]
    with InferenceEngine(whole, max_batch_size=4) as engine:
        engine.prefill_session("s", ids[:, :-1], chunk=8, cache_len=32)
        grown = -held_picks("L1_moe")
        out = engine.generate("s", ids[:, -1:], 2)
    assert grown + held_picks("L1_moe") == int(
        out.expert_tokens["L1_moe"].sum()) == 3 * 2 * 2


# ------------------------------------------------------- vertices, scopes
def test_stream_vertices():
    x = acts((2, 3, 5))
    streams = StreamExpandVertex(n_streams=4).apply(x)
    assert streams.shape == (2, 3, 4, 5)
    np.testing.assert_allclose(StreamSumVertex().apply(streams), 4 * x,
                               rtol=1e-6)


def test_the_parts_a_trace_has_to_tell_apart_have_scopes_of_their_own(net):
    text = net._token_step_fn.lower(
        net.params, net.net_state, net._init_carries(2, cache_len=8),
        jnp.zeros((2, 1), jnp.int32), net.zero_expert_counts()).as_text(
            debug_info=True)
    for scope in ("layer.L1_moe.experts", "layer.L1_moe.router",
                  "layer.L1_moe.shared", "layer.L2_attn.latent_attention",
                  "layer.L0_ffn_write.sinkhorn", "layer.L0_attn_read"):
        assert f"/{scope}/" in text, scope
    # the token step keeps the experts' dense form: three plain products
    # over the matrices under the experts' scope (and the 0/1 one that
    # spreads a weight over its expert's columns) and no kernel (a few
    # tokens are bound by the experts' bytes, which both forms read once)
    experts = [line.split('"')[1] for line in text.splitlines()
               if line.startswith("#loc") and "/layer.L1_moe.experts/" in line]
    assert sum(name.endswith("/dot_general") for name in experts) == 4
    assert not any("pallas_call" in name for name in experts)
    assert monitor.parse_op_name(
        "jit(run)/layer.L1_moe/layer.L1_moe.experts/dot_general") == (
            "layer.L1_moe.experts", "forward")
    with monitor.subscope("alone"):                  # no scope open
        pass


def test_the_streamed_kernel_carries_the_scope_it_is_called_under(
        monkeypatch):
    """A Pallas kernel takes the name stack it is called under like any
    other operation: with the streamed form chosen (the predicate told
    that Mosaic is there; 8 heads and rings of 256 slots, two blocks),
    the token step's ``pallas_call`` carries
    ``layer.<vertex>.latent_attention`` and ``parse_op_name`` gives it
    that row, so the kernel's device seconds land where
    ``mla_decode_roofline`` reads them."""
    from deeplearning4j_tpu.ops import attention
    monkeypatch.setattr(attention, "_mosaic", lambda: True)
    net = ComputationGraph(from_config(
        {**CFG, "num_attention_heads": 8}, dtype="float32",
        **{**ORDER_ONE, "cache_len": 256})).init()
    assert net.vertices["L1_attn"].layer.attention_path(
        1, net._init_carries(2, cache_len=256)["L1_attn"]) == "streamed"
    text = net._token_step_fn.lower(
        net.params, net.net_state, net._init_carries(2, cache_len=256),
        jnp.zeros((2, 1), jnp.int32), net.zero_expert_counts()).as_text(
            debug_info=True)
    names = [line.split('"')[1] for line in text.splitlines()
             if "/layer.L1_attn.latent_attention/pallas_call" in line]
    assert names, "no pallas_call under layer.L1_attn.latent_attention"
    assert {monitor.parse_op_name(n) for n in names} == {
        ("layer.L1_attn.latent_attention", "forward")}


def test_the_plain_paths_adds_carry_their_vertex_scope(nets):
    """The residual adds are vertices, so they sit under
    ``layer.<vertex>`` like a layer: nothing of the plain path's step is
    left without a scope for ``by_scope`` to miss."""
    _, flat, _ = nets["plain"]
    text = flat._token_step_fn.lower(
        flat.params, flat.net_state, flat._init_carries(2, cache_len=8),
        jnp.zeros((2, 1), jnp.int32), flat.zero_expert_counts()).as_text(
            debug_info=True)
    for scope in ("layer.L0_attn_add", "layer.L2_ffn_add",
                  "layer.L1_moe.experts", "layer.L1_moe.router",
                  "layer.L1_moe.shared", "layer.L2_attn.latent_attention"):
        assert f"/{scope}/" in text, scope
    assert "sinkhorn" not in text and "stream" not in text
    adds = [line.split('"')[1] for line in text.splitlines()
            if line.startswith("#loc") and "/layer.L1_ffn_add/" in line]
    assert adds and all(name.endswith("/add") for name in adds)
    assert monitor.parse_op_name(adds[0]) == ("layer.L1_ffn_add", "forward")


# ------------------------------------------------- weights laid once (PR 38)
def _laid_bytes(vertex):
    return monitor.gauge("serving_laid_weight_bytes", "").value(
        vertex=vertex)


def _same_leaves(a, b):
    a, b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float64),
                                      np.asarray(y, np.float64))


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_laid_forms_step_bit_for_bit_as_the_stored_parameters(
        ids, dtype, kind):
    """A served net hands its steps the forms it laid once; handed the
    stored parameters (a pinned version, a net not prepared) the layer
    lays them inside the step: the same products on the same numbers.
    A prefill chunk and a token step, every ring, id and logit."""
    served = build(dtype, for_inference=True, kind=kind)
    attn = [n for n in served._layer_names() if n.endswith("_attn")]
    assert served.laid_vertices() == attn
    laid = served.served_params()
    layer = served.vertices["L1_attn"].layer
    # the stored tree keeps every name; the laid one swaps two matrices
    # for the four forms and shares every other leaf
    assert sorted(served.params["L1_attn"]) == sorted(layer.param_order())
    assert sorted(laid["L1_attn"]) == sorted(
        set(layer.param_order()) - set(layer.LAID_FROM)
        | {"Wq_nope", "Wq_rope", "Wk_absorbed", "Wv"})
    assert laid["L1_attn"]["Wo"] is served.params["L1_attn"]["Wo"]
    assert laid["L1_moe"] is served.params["L1_moe"]
    assert [laid["L1_attn"][k].shape for k in (
        "Wq_nope", "Wq_rope", "Wk_absorbed", "Wv")] == [
            (4, 16, 48), (4, 2, 4, 48), (4, 16, 32), (4, 32, 16)]
    forms = sum(laid["L1_attn"][k].nbytes for k in (
        "Wq_nope", "Wq_rope", "Wk_absorbed", "Wv"))
    assert forms == sum(served.params["L1_attn"][k].nbytes
                        for k in layer.LAID_FROM)
    assert _laid_bytes("L1_attn") == forms
    carries = lambda: served._init_carries(3, cache_len=32)
    a = served.prefill_step(carries(), ids[:, :8])
    b = served.prefill_step(carries(), ids[:, :8], params=served.params)
    _same_leaves(a, b)
    a = served.token_step(a, ids[:, 8:9])
    b = served.token_step(b, ids[:, 8:9], params=served.params)
    _same_leaves(a, b)
    # and a net that was not prepared takes the stored form by itself
    plain_net = build(dtype, kind=kind)
    assert plain_net.laid_vertices() == []
    assert plain_net.served_params() is plain_net.params
    c = plain_net.token_step(
        plain_net.prefill_step(plain_net._init_carries(3, cache_len=32),
                               ids[:, :8]), ids[:, 8:9])
    _same_leaves(a, c)


def _parent_attention(layer, p, x):
    """``LatentAttention.forward`` as the parent commit wrote it: the
    stored matrices multiplied whole, then reshaped and sliced."""
    b, t = x.shape[:2]
    h, dn, dr, dv = layer.n_heads, layer.d_nope, layer.d_rope, layer.d_v
    positions = jnp.arange(t, dtype=jnp.int32)
    inv_freq, factor = decoder.yarn_inv_freq(dr, layer.rope_theta,
                                             layer.rope_scaling)
    turn = lambda a: decoder.rotate(a, positions, inv_freq, factor)
    c_q = decoder.rms_normalize(x @ p["Wqa"], layer.eps,
                                p["q_gain"]).astype(x.dtype)
    q = (c_q @ p["Wqb"]).reshape(b, t, h, dn + dr)
    q_nope, q_rope = q[..., :dn], turn(q[..., dn:])
    kv = x @ p["Wkva"]
    c_kv = decoder.rms_normalize(kv[..., :layer.kv_rank], layer.eps,
                                 p["kv_gain"]).astype(x.dtype)
    k_rope = turn(kv[..., layer.kv_rank:])
    wkvb = p["Wkvb"].reshape(layer.kv_rank, h, dn + dv)
    q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, wkvb[..., :dn])
    ctx = latent_ring_attention_dense(
        q_lat, q_rope, c_kv, k_rope, jnp.zeros((), jnp.int32),
        sm_scale=layer.sm_scale())
    out = jnp.einsum("bthr,rhd->bthd", ctx, wkvb[..., dn:])
    return out.reshape(b, t, -1) @ p["Wo"]


@pytest.mark.parametrize("dtype,bound", [("float32", 1e-6),
                                         ("bfloat16", 2e-2)])
def test_output_and_gradient_are_the_parents(net, dtype, bound):
    """``forward`` (what ``output()`` and ``fit`` run) lays the stored
    parameters inside the program: the parent's numbers up to the order
    of a product's sums, value and gradient."""
    layer = net.vertices["L1_attn"].layer
    p = jax.tree.map(lambda a: a.astype(dtype), net.params["L1_attn"])
    x = acts((2, 9, 64)).astype(dtype)

    def ours(p, x):
        return layer.forward(p, {}, x, train=True)[0]

    def loss(f):
        return lambda p, x: jnp.sum(
            f(p, x).astype(jnp.float32) * jnp.cos(jnp.arange(64.0)))

    assert rel(ours(p, x), _parent_attention(layer, p, x)) < bound
    got = jax.grad(loss(ours), argnums=(0, 1))(p, x)
    want = jax.grad(loss(lambda p, x: _parent_attention(layer, p, x)),
                    argnums=(0, 1))(p, x)
    assert sorted(got[0]) == sorted(layer.param_order())
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert rel(g, w) < bound


def test_a_served_net_is_written_as_any_other(tmp_path, nets):
    """``params`` keeps every name and published shape, and what the
    serializer writes of a served net is what it writes of one that was
    not prepared: the laid forms are held beside the parameters, not
    among them."""
    import zipfile
    from deeplearning4j_tpu.utils import model_serializer
    _, flat, _ = nets["plain"]
    served = build(for_inference=True, kind="plain")
    assert {n: {k: a.shape for k, a in p.items()}
            for n, p in served.params.items()} == {
                n: {k: a.shape for k, a in p.items()}
                for n, p in flat.params.items()}
    assert served.num_params() == flat.num_params()
    paths = [str(tmp_path / name) for name in ("served.zip", "flat.zip")]
    for model, path in zip((served, flat), paths):
        model_serializer.write_model(model, path, save_updater=False)
    a, b = (zipfile.ZipFile(path) for path in paths)
    assert sorted(a.namelist()) == sorted(b.namelist())
    assert (a.read(model_serializer.COEFFICIENTS_BIN)
            == b.read(model_serializer.COEFFICIENTS_BIN))
    back = model_serializer.restore_computation_graph(paths[0])
    _same_leaves(back.params, served.params)


def _stepped(model, ids, **kw):
    """Ids and kept logits of one token step after a prefill chunk."""
    carries = model.prefill_step(model._init_carries(3, cache_len=32),
                                 ids[:, :8], **kw)
    return model.token_step(carries, ids[:, 8:9], **kw)[:2]


@pytest.mark.parametrize("how", ["assigned", "loaded", "one_leaf"])
def test_replaced_parameters_are_laid_again(tmp_path, ids, how):
    """The laid forms follow the stored parameters however they are
    replaced: ``params`` assigned, a saved net's weights loaded leaf by
    leaf (``set_flat_params``), one matrix swapped in place.  The next
    step computes with the new weights, and the gauge is set again."""
    from deeplearning4j_tpu.utils import model_serializer
    served = build(for_inference=True, kind="plain")
    other = build(kind="plain", seed=11)
    before = _stepped(served, ids)
    _same_leaves(before, _stepped(served, ids, params=served.params))
    gauge = monitor.gauge("serving_laid_weight_bytes", "")
    want = _laid_bytes("L1_attn")
    for vertex in served.laid_vertices():
        gauge.set(0, vertex=vertex)
    if how == "assigned":
        served.params = jax.tree.map(jnp.copy, other.params)
    elif how == "loaded":
        path = str(tmp_path / "other.zip")
        model_serializer.write_model(other, path, save_updater=False)
        served.set_flat_params(model_serializer.restore_computation_graph(
            path).get_flat_params())
    else:
        served.params["L1_attn"]["Wkvb"] = jnp.copy(
            other.params["L1_attn"]["Wkvb"])
    after = _stepped(served, ids)
    relaid = (["L1_attn"] if how == "one_leaf" else served.laid_vertices())
    assert [v for v in served.laid_vertices() if _laid_bytes(v)] == relaid
    assert _laid_bytes("L1_attn") == want
    _same_leaves(after, _stepped(served, ids, params=served.params))
    assert not np.array_equal(np.asarray(after[1]), np.asarray(before[1]))
    if how != "one_leaf":
        _same_leaves(after, _stepped(other, ids))
    # nothing replaced: nothing laid again
    for vertex in served.laid_vertices():
        gauge.set(0, vertex=vertex)
    _same_leaves(after, _stepped(served, ids))
    assert not any(_laid_bytes(v) for v in served.laid_vertices())


def test_a_served_session_counts_its_steps_as_laid(ids):
    """``latent_attention_steps_total{weights}``: a served net's steps
    multiply the laid forms; the same net stepping a pinned version's
    tree (handed to the step as stored) says so."""
    def steps(weights):
        return monitor.counter("latent_attention_steps_total", "").value(
            path="dense", weights=weights)

    served = build(for_inference=True, kind="plain")
    before = steps("laid"), steps("stored")
    with InferenceEngine(served, max_batch_size=4) as engine:
        engine.prefill_session("s", ids[:, :-1], chunk=8, cache_len=32)
        engine.generate("s", ids[:, -1:], 3)
        assert (steps("laid"), steps("stored")) == (before[0] + 3,
                                                    before[1])
        # a deploy's swap: sessions made after it are pinned to the
        # staged tree, which reaches the step as it is stored
        engine.swap_weights(jax.tree.map(np.asarray, served.params))
        engine.prefill_session("t", ids[:, :-1], chunk=8, cache_len=32)
        engine.generate("t", ids[:, -1:], 2)
    assert (steps("laid"), steps("stored")) == (before[0] + 3,
                                                before[1] + 2)
