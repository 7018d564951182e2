"""The latent-attention, routed-expert, hyper-connected decoder at a
small size on the CPU: every new layer against the plain reference
(``benchmark/reference/mla_moe_decoder.py``, which shares no code with
the package's layers), the served path (chunked prefill, fork, token
generation through ``InferenceEngine`` sessions) against the
reference's full forward, and planted faults that the comparison has to
catch."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mla_moe_decoder as ref
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.models.mla_moe_decoder import from_config
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.conf.computation_graph import (
    ComputationGraphConfiguration, StreamExpandVertex, StreamSumVertex)
from deeplearning4j_tpu.nn.layers import decoder
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
#: bf16 against the float32 reference at this size: 0.012 measured
#: (8 significant bits, ~40 roundings between ids and logits, and a
#: routing choice or two flipped); four times that.  Weights rounded to
#: float8 read 0.20.
BF16_BOUND = 0.05


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def build(dtype=None, for_inference=False, **kw):
    return ComputationGraph(from_config(
        CFG, dtype=dtype, **{**ORDER_ONE, **kw})).init(
            for_inference=for_inference)


@pytest.fixture(scope="module")
def net():
    return build()


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


# -------------------------------------------------------- the whole model
def test_output_agrees_with_the_reference_and_json_round_trips(net, ids):
    want = ref.forward(CFG, net.params, ids)
    assert rel(net.output(ids), want) < 1e-5
    conf = from_config(CFG, **ORDER_ONE)
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert json.loads(again.to_json()) == json.loads(conf.to_json())
    np.testing.assert_array_equal(
        ComputationGraph(again).init().output(ids), net.output(ids))


@pytest.mark.parametrize("fault", ["no_shared_expert", "no_routed_scaling",
                                   "rotary_off", "streams_as_one"])
def test_a_planted_fault_fails_the_comparison(net, ids, fault):
    """A program that left out the shared expert, the routed scaling
    factor or the rotary embedding, or mixed its streams as one, reads
    as the reference with that fault planted reads against the sound
    one: far over any bound here."""
    sound = ref.forward(CFG, net.params, ids)
    assert rel(ref.forward(CFG, net.params, ids, faults=(fault,)),
               sound) > 2 * BF16_BOUND


def test_prefill_in_chunks_then_decode_agrees_with_the_full_forward(net, ids):
    want = np.asarray(ref.forward(CFG, net.params, ids))
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


def test_bf16_serving_net_holds_two_bytes_a_parameter_and_stays_in_bound(ids):
    served = build("bfloat16", for_inference=True)
    leaves = jax.tree.leaves(served.params)
    assert {str(a.dtype) for a in leaves} == {"bfloat16"}
    assert all(state == {} for state in served.updater_state.values())
    n = sum(a.size for a in leaves)
    assert sum(a.nbytes for a in leaves) == 2 * n
    with pytest.raises(ValueError, match="for_inference"):
        served.fit(np.zeros((1, 4), np.int32), np.zeros((1, 4, 256)))
    want = np.asarray(ref.forward(CFG, served.params, ids))
    assert 1e-4 < rel(served.output(ids), want) < BF16_BOUND
    # the control: the same comparison with the matrices rounded one
    # step lower (float8) has to fail
    low = ref.forward(CFG, served.params, ids, fp8_weights=True)
    assert rel(served.output(ids), low) > 2 * BF16_BOUND
    carries = served._init_carries(3, cache_len=32)
    assert carries["L0_attn"][0].dtype == jnp.bfloat16
    with InferenceEngine(served, max_batch_size=4) as engine:
        engine.prefill_session("s", ids[:, :-1], chunk=8, cache_len=32)
        out = engine.generate("s", ids[:, -1:], 4)
    kept = np.stack([np.asarray(k) for k in out.kept_logits], axis=1)
    sequence = np.concatenate([ids, out.ids[:, :-1]], axis=1)
    want = np.asarray(ref.forward(CFG, served.params, sequence, last=4))
    assert rel(kept, want[[0, 2]]) < BF16_BOUND


def test_inference_init_draws_the_same_parameters(net):
    served = build(for_inference=True)
    for a, b in zip(jax.tree.leaves(net.params),
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
    # under the experts' scope and no kernel (a few tokens are bound by
    # the experts' bytes, which both forms read once)
    experts = [line.split('"')[1] for line in text.splitlines()
               if line.startswith("#loc") and "/layer.L1_moe.experts/" in line]
    assert sum(name.endswith("/dot_general") for name in experts) == 3
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
