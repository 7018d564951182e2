"""The decoder whose latent attention layers are full ones over the rows
an indexer selects (a ring that grows) beside a window's (a ring that
wraps), each gated headwise, with a leading dense layer and
sigmoid-routed experts under a share beside a shared expert, at a small
size on the CPU: the served path against the plain reference
(``benchmark/reference/mla_sparse_window_moe.py``, which shares no code
with the package) with a prompt several window rings long and a context
past the indexer's ``topk``; planted faults the comparison has to
catch; the streamed kernel (interpreted) and every form of the two
attentions against their dense oracles; the shares tied to the uncut
layer; three kinds of ring in one session cache; the builder's keys and
the files it refuses."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mla_sparse_window_moe as ref
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.models.mla_moe_decoder import from_config
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import decoder
from deeplearning4j_tpu.ops import attention
from deeplearning4j_tpu.serving import InferenceEngine, SessionCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW, CHUNK, TOPK = 5, 7, 8
HELD = [4, 5, 6, 7]
#: the keys of ``benchmark/configs/dots3_note_prev.json`` at a small size:
#: full layers of 4 heads at ranks 64 / 16 with an indexer of 4 heads of
#: 16 (8 of them turned) picking 8 rows; window layers of 2 heads at
#: ranks 64 / 24 over a window of 5; both gated headwise; a leading dense
#: layer, then 16 sigmoid-routed experts of which this chip holds ids
#: 4-7, 3 a token, beside a shared one.  The hidden size is the query
#: rank, so that the planted fault "the indexer's query from x" can be
#: computed at all
CFG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=5,
    intermediate_size=48, moe_intermediate_size=32, n_routed_experts=4,
    n_shared_experts=1, num_experts_per_tok=3, norm_topk_prob=True,
    scoring_func="sigmoid", routed_scaling_factor=1.0,
    topk_method="noaux_tc", first_k_dense_replace=1, moe_layer_freq=1,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=64,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=8e7, rope_scaling=None, rms_norm_eps=1e-5,
    index_n_heads=4, index_head_dim=16, index_topk=TOPK,
    attention_gate_type="headwise", swa_attention_gate_type="headwise",
    sliding_window_size=WINDOW, swa_q_lora_rank=64, swa_kv_lora_rank=24,
    swa_num_attention_heads=2, swa_num_key_value_heads=2,
    swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
    swa_rope_theta=5e4,
    layer_types=["full_attention"] * 2 + ["sliding_attention"] * 3
    + ["full_attention"] + ["sliding_attention"] * 3,
    tie_word_embeddings=False, max_position_embeddings=4096,
    published={"n_routed_experts": 16}, builder_args={"experts_held": HELD})
ARGS = dict(cache_len=320, init_std=0.3, seed=3, experts_held=HELD,
            max_chunk=CHUNK, router_bias_std=0.05, dtype="float32")
#: a window ring's slots here: 5 + 7 - 1 in whole blocks of 128
SLOTS = 128


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def build(cfg=CFG, **kw):
    return ComputationGraph(from_config(cfg, **{**ARGS, **kw})).init()


@pytest.fixture(scope="module")
def net():
    return build()


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(0).randint(0, 256, (2, 300)).astype(np.int32)


@pytest.fixture(scope="module")
def served(net, ids):
    """Prefill in chunks of 7 (280 positions: the window rings of 128
    slots wrap twice and chunks straddle the wrap; every query past the
    eighth selects), then 19 token steps: ``(kept logits (2 rows, 19,
    vocabulary), the whole sequence)``."""
    cache = SessionCache(net, name="served", ttl_s=0.0)
    cache.prefill("s", ids[:, :280], chunk=CHUNK, cache_len=320)
    gen = cache.generate("s", ids[:, 280:281], 19)
    kept = np.stack([np.asarray(k) for k in gen.kept_logits], axis=1)
    return kept, np.concatenate([ids[:, :281], gen.ids[:, :-1]], axis=1)


# ------------------------------------------------ program and reference
def test_prefill_then_token_steps_equal_the_references_full_forward(
        net, served):
    kept, sequence = served
    assert sequence.shape[1] == 299 > 2 * SLOTS > 30 * TOPK
    want = np.asarray(ref.forward(CFG, net.params, sequence, last=19))
    assert rel(kept[0], want[0]) < 1e-5 and rel(kept[1], want[1]) < 1e-5
    # every generated position on its own
    assert max(rel(k, w) for k, w in zip(kept[0], want[0])) < 1e-4


def test_output_agrees_with_the_reference(net, ids):
    assert rel(net.output(ids[:, :40]),
               ref.forward(CFG, net.params, ids[:, :40])) < 1e-5


@pytest.mark.parametrize("fault", [
    {"faults": ("window_plus_one",)}, {"no_gate": True},
    {"faults": ("index_rope_all",)}, {"faults": ("index_query_from_x",)},
    {"dense_attention": True}, {"all_full": True}, {"fp8_weights": True}])
def test_a_planted_fault_fails_the_comparison(net, served, fault):
    """A window of ``W + 1``, no gate, the indexer's rotary over its
    whole head, the indexer's query from ``x`` instead of ``c_q``, no
    selection, no window, float8 weights: each reads far over the
    float32 limit."""
    kept, sequence = served
    wrong = np.asarray(ref.forward(CFG, net.params, sequence, last=19,
                                   **fault))
    assert rel(kept[0], wrong[0]) > 0.01, fault


def test_the_reference_takes_any_query_block(net, ids):
    want = ref.forward(CFG, net.params, ids[:, :61])
    for block in (5, 16, 61, 200):
        assert rel(ref.forward(CFG, net.params, ids[:, :61],
                               query_block=block), want) < 1e-5, block


# ------------------------------------------------------------ the kernels
def _ring(cap, width, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(2, cap, width),
                       jnp.float32)


@pytest.mark.parametrize("window,cursor,t", [
    (None, 0, 8), (None, 37, 1), (None, 40, 8), (None, 50, 5),
    (40, 3, 1), (40, 60, 8), (40, 250, 1),          # before the wrap
    (40, 250, 8), (40, 255, 1),                     # at it: a chunk straddles
    (40, 300, 16), (40, 520, 5), (40, 700, 1)])     # and after
def test_the_streamed_kernel_agrees_with_the_masked_form(window, cursor, t):
    """Interpreted, blocks of 64 slots in a ring of 256: with a window of
    40 a call sees at most two blocks, so blocks are skipped before the
    window and beyond the cursor, also where the walk wraps."""
    cap, heads, rank, width = 256, 4, 32, 64
    q = jnp.asarray(np.random.RandomState(1).randn(2, t, heads, width),
                    jnp.float32)
    ring = _ring(cap, width)
    keep = (attention.ring_visible(cursor, t, cap, window) if window
            else attention.visible_slots(cursor, t, cap))
    want = attention.latent_attention_masked(
        q, ring, jnp.broadcast_to(keep[None], (2,) + keep.shape), rank=rank,
        sm_scale=0.2)
    got = attention.latent_attention_streamed(
        q, ring, cursor, rank=rank, sm_scale=0.2, window=window, block=64,
        interpret=True)
    assert got.shape == (2, t, heads, rank) and rel(got, want) < 1e-5


def test_the_kernel_walks_only_the_blocks_that_hold_a_visible_position():
    """A NaN in any block the walk should skip would poison the result:
    the blocks before the window and beyond the cursor are NaN."""
    cap, heads, rank, width, window, cursor = 256, 4, 32, 64, 40, 700
    ring = _ring(cap, width)
    # positions 661..700 are seen: slots 149..188, blocks 2 of 64
    poisoned = ring.at[:, :128].set(jnp.nan).at[:, 192:].set(jnp.nan)
    q = jnp.asarray(np.random.RandomState(2).randn(2, 1, heads, width),
                    jnp.float32)
    got = attention.latent_attention_streamed(
        q, poisoned, cursor, rank=rank, sm_scale=0.2, window=window,
        block=64, interpret=True)
    keep = attention.ring_visible(cursor, 1, cap, window)
    want = attention.latent_attention_masked(
        q, ring, jnp.broadcast_to(keep[None], (2, 1, cap)), rank=rank,
        sm_scale=0.2)
    assert bool(jnp.isfinite(got).all()) and rel(got, want) < 1e-5


def test_the_kernel_keeps_what_the_selection_marks():
    cap, t, cursor, heads, rank, width = 512, 8, 300, 4, 32, 64
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, t, heads, width), jnp.float32)
    ring = _ring(cap, width, 4)
    picked = (jnp.asarray(rng.rand(2, t, cap) < 0.3)
              & attention.visible_slots(cursor, t, cap)[None])
    picked = picked.at[:, :, 0].set(True)
    want = attention.latent_attention_masked(q, ring, picked, rank=rank,
                                             sm_scale=0.2)
    got = attention.latent_attention_streamed(
        q, ring, cursor, rank=rank, sm_scale=0.2,
        selected=picked.astype(jnp.bfloat16), block=128, interpret=True)
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("t,cursor,cap", [(1, 90, 256), (1, 300, 2048),
                                          (1, 5, 2048), (8, 300, 2048),
                                          (5, 60, 256)])
def test_every_form_of_the_sparse_attention_agrees_with_the_masked_one(
        monkeypatch, t, cursor, cap):
    """With Mosaic pretended, the path picks the gathered form for a
    token step over a ring at least 12 x ``topk`` long (the listed rows
    taken out of the ring) and the streamed form (a mask) otherwise; the
    kernels run interpreted and agree with the dense form, which picks
    the same rows."""
    heads, rank, width, d, j, topk = 4, 128, 256, 128, 4, 128
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(2, t, heads, width), jnp.float32)
    ring = _ring(cap, width, 6)
    i_ring = jnp.asarray(rng.randn(2, cap, d), jnp.float32)
    q_idx = jnp.asarray(rng.randn(2, t, j, d), jnp.float32)
    w_idx = jnp.asarray(rng.randn(2, t, j), jnp.float32)
    kw = dict(rank=rank, topk=topk, sm_scale=0.1)
    want, _ = attention.sparse_latent_attention(q, q_idx, w_idx, ring,
                                                i_ring, cursor, **kw)
    monkeypatch.setattr(attention, "_mosaic", lambda: True)
    path = attention.latent_walk_path(t, heads, width, rank, cap,
                                      jnp.float32, topk)
    assert path == ("gathered" if t == 1 and cap >= 12 * topk
                    else "streamed")
    got, tied = attention.sparse_latent_attention(q, q_idx, w_idx, ring,
                                                  i_ring, cursor, **kw)
    assert rel(got, want) < 1e-5 and int(tied) == 0


def test_the_indexer_reads_a_lane_wide_key_ring_as_it_lies(monkeypatch):
    """A 128-wide key ring is read slot after slot, a narrower one with
    its slots minor: the same scores as the plain form either way."""
    rng = np.random.RandomState(7)
    for d in (64, 128):
        for t in (1, 8):
            q_idx = jnp.asarray(rng.randn(2, t, 4, d), jnp.float32)
            w_idx = jnp.asarray(rng.randn(2, t, 4), jnp.float32)
            i_ring = jnp.asarray(rng.randn(2, 1024, d), jnp.float32)
            want = attention.indexer_scores(q_idx, w_idx, i_ring)
            got = attention.indexer_scores_streamed(q_idx, w_idx, i_ring, 1023,
                                                    interpret=True)
            assert rel(got, want) < 1e-5, (d, t)


def test_the_path_is_chosen_from_the_shapes(monkeypatch):
    path = attention.latent_walk_path
    full = dict(heads=128, width=640, rank=512, capacity=32768,
                dtype=jnp.bfloat16, topk=2048)
    window = dict(heads=64, width=1152, rank=1024, capacity=640,
                  dtype=jnp.bfloat16)
    assert path(1, **full) == path(1, **window) == "masked"  # no Mosaic
    monkeypatch.setattr(attention, "_mosaic", lambda: True)
    assert path(1, **full) == "gathered"
    assert path(64, **full) == path(63, **full) == "streamed"
    assert path(1, **{**full, "capacity": 16384}) == "streamed"
    assert path(1, **window) == path(64, **window) == "streamed"
    assert path(640, **window) == "masked"          # output() from zero
    assert path(1, **{**window, "rank": 24}) == "masked"
    assert path(1, **{**full, "dtype": jnp.float64}) == "masked"
    assert path(1, **{**full, "capacity": 32767}) == "masked"
    assert attention.joined_width(512, 64) == 640
    assert attention.joined_width(1024, 64) == 1152
    assert attention.window_ring_slots(513, 64) == 640


# ------------------------------------------------------------ the layers
def test_the_layer_kinds_and_their_rings(net):
    full, window = (net.vertices[n].layer for n in ("L1_attn", "L2_attn"))
    assert (full.STATE_KIND, window.STATE_KIND) == ("sparse_latent",
                                                    "window_latent")
    assert (full.RING_GROWS, window.RING_GROWS) == (True, False)
    ring, i_ring, cursor = full.init_carry(3, jnp.float32, 48)
    assert ring.shape == (3, 48, 128) and i_ring.shape == (3, 48, 16)
    assert [a.shape[1] for a in full.grow_carry((ring, i_ring, cursor),
                                                96)[:2]] == [96, 96]
    for asked in (None, 16, 4096):       # its own size whatever it is handed
        ring, cursor = window.init_carry(3, jnp.float32, asked)
        assert ring.shape == (3, SLOTS, 128) and cursor.shape == ()
    assert window.grow_carry((ring, cursor), 4096)[0] is ring
    # a plain latent layer keeps its two rings
    plain = decoder.LatentAttention(n_in=64, n_out=64, n_heads=2, q_rank=8,
                                    kv_rank=16, d_nope=8, d_rope=4, d_v=8)
    assert plain.STATE_KIND == "latent" and plain.RING_GROWS
    assert [a.shape for a in plain.init_carry(1, jnp.float32, 4)] == [
        (1, 4, 16), (1, 4, 4), ()]
    assert plain.LAID_FROM == ("Wqb", "Wkvb")
    assert full.LAID_FROM == ("Wqb", "Wkvb", "WqI")
    with pytest.raises(ValueError, match="under a window of 5"):
        window.forward_seq(net.params["L2_attn"],
                           jnp.zeros((1, SLOTS - WINDOW + 2, 64)),
                           window.init_carry(1, jnp.float32), train=False)


def test_the_gate_multiplies_each_heads_output(net):
    """The gate's matrix at zero halves every head: the layer's output
    is then half the ungated one."""
    layer = net.vertices["L2_attn"].layer
    p = dict(net.params["L2_attn"])
    x = jnp.asarray(np.random.RandomState(8).randn(2, 9, 64), jnp.float32)
    ungated = decoder.LatentAttention(**{
        **{f.name: getattr(layer, f.name)
           for f in layer.__dataclass_fields__.values()}, "gate": False})
    assert "Wg" not in ungated.param_order() and "Wg" in layer.param_order()
    plain = ungated.forward({k: v for k, v in p.items() if k != "Wg"}, {},
                            x, train=False)[0]
    half = layer.forward({**p, "Wg": jnp.zeros_like(p["Wg"])}, {}, x,
                         train=False)[0]
    assert rel(half, 0.5 * plain) < 1e-6
    assert rel(layer.forward(p, {}, x, train=False)[0], plain) > 0.05


def test_laid_and_stored_weights_give_the_same_numbers(ids):
    """A served net multiplies the laid forms (``WqI`` among them); a
    net not prepared lays them inside the step: the same logits."""
    served_net = build(seed=5)
    served_net._inference_only = True
    laid = served_net.served_params()
    assert "Wq_index" in laid["L0_attn"] and "WqI" not in laid["L0_attn"]
    assert laid["L0_attn"]["Wq_index"].shape == (4, 16, 64)
    plain = build(seed=5)
    got = [SessionCache(n, name=f"lay{i}", ttl_s=0.0) for i, n in
           enumerate((served_net, plain))]
    outs = []
    for cache in got:
        cache.prefill("s", ids[:, :30], chunk=CHUNK, cache_len=64)
        gen = cache.generate("s", ids[:, 30:31], 3)
        outs.append(np.stack([np.asarray(k) for k in gen.kept_logits]))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_the_shares_of_all_holders_add_up_to_the_uncut_layer():
    """Four chips hold ids 0-3, 4-7, 8-11, 12-15 of a router of 16: the
    shares' routed parts, with the shared expert (which every chip
    computes alike) counted once, are the uncut layer's."""
    x = jnp.asarray(np.random.RandomState(4).randn(2, 9, 64), jnp.float32)
    cut = lambda held: {**CFG, "n_routed_experts": len(held),
                        "builder_args": {"experts_held": held}}
    whole_cfg = {**CFG, "n_routed_experts": 16, "published": {}}
    whole = build(whole_cfg, experts_held=None)
    layer = whole.vertices["L1_moe"].layer
    want = layer.forward(whole.params["L1_moe"], layer.init_state(), x,
                         train=False)[0]
    sg, su, sd = (np.asarray(whole.params["L1_moe"][k], np.float64)
                  for k in ("Sg", "Su", "Sd"))
    flat = np.asarray(x, np.float64)
    gate = flat @ sg
    shared_once = ((gate / (1 + np.exp(-gate))) * (flat @ su)) @ sd
    total = 0
    for held in ([0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11],
                 [12, 13, 14, 15]):
        share = build(cut(held), experts_held=held)
        part = share.vertices["L1_moe"].layer
        p = share.params["L1_moe"]
        np.testing.assert_array_equal(p["router_bias"],
                                      whole.params["L1_moe"]["router_bias"])
        y = part.forward(p, part.init_state(), x, train=False)[0]
        total = total + np.asarray(y, np.float64) - shared_once
    assert rel(total + shared_once, want) < 1e-5


# --------------------------------------------------------------- sessions
def test_three_kinds_of_ring_in_one_cache(net, ids):
    """A joined latent ring and an indexer-key ring that grow beside a
    window's joined latent ring that wraps: bytes by kind, a fork that
    leaves the snapshot intact, a position far past the window rings."""
    with InferenceEngine(net, max_batch_size=2, name="three") as engine:
        cache = engine.sessions
        engine.prefill_session("snap", ids[:, :280], chunk=CHUNK,
                               cache_len=320)
        held = jax.tree.map(np.asarray, cache.get_carries("snap"))
        assert {n: c[0].shape[1] for n, c in held.items()} == {
            "L0_attn": 320, "L1_attn": 320, "L2_attn": SLOTS,
            "L3_attn": SLOTS, "L4_attn": SLOTS}
        assert cache.session_position("snap") == 280 > 2 * SLOTS
        engine.fork_session("snap", "a")
        a = engine.generate("a", ids[:, 280:281], 6)
        engine.fork_session("snap", "b")
        b = engine.generate("b", ids[:, 280:281], 6)
        np.testing.assert_array_equal(a.ids, b.ids)
        for kept, now in zip(jax.tree.leaves(held), jax.tree.leaves(
                jax.tree.map(np.asarray, cache.get_carries("snap")))):
            np.testing.assert_array_equal(kept, now)
        value = lambda kind: monitor.gauge(
            "serving_session_state_bytes", "").value(model="three", kind=kind)
        # float32: a full layer's slot 128 + 16 numbers, a window's 128
        assert value("sparse_latent") == 3 * 2 * (2 * 320 * 144 * 4 + 4)
        assert value("window_latent") == 3 * 3 * (2 * SLOTS * 128 * 4 + 4)
        assert cache.state_bytes() == value("sparse_latent") + value(
            "window_latent")
        assert sorted(a.selection_tied) == ["L0_attn", "L1_attn"]
    kept = np.stack([np.asarray(k) for k in a.kept_logits], axis=1)
    sequence = np.concatenate([ids[:, :281], a.ids[:, :-1]], axis=1)
    assert rel(kept, ref.forward(CFG, net.params, sequence, last=6)) < 1e-5


def test_token_steps_are_counted_by_kind_form_and_weights(net, ids):
    count = lambda kind: monitor.counter(
        "latent_attention_steps_total", "").value(
            kind=kind, path="masked", weights="stored")
    before = {kind: count(kind) for kind in ("sparse", "window")}
    cache = SessionCache(net, name="counted", ttl_s=0.0)
    cache.prefill("s", ids[:, :21], chunk=CHUNK, cache_len=64)
    cache.generate("s", ids[:, 21:22], 4)
    for kind in ("sparse", "window"):       # the 4 token steps, not chunks
        assert count(kind) - before[kind] == 4


def test_the_parts_a_trace_has_to_tell_apart_have_scopes_of_their_own(net):
    text = net._token_step_fn.lower(
        net.params, net.net_state, net._init_carries(2, cache_len=64),
        jnp.zeros((2, 1), jnp.int32), net.zero_expert_counts()).as_text(
            debug_info=True)
    for scope in ("layer.L0_attn.indexer", "layer.L0_attn.select",
                  "layer.L0_attn.sparse_latent_attention",
                  "layer.L2_attn.window_latent_attention",
                  "layer.L1_moe.experts", "layer.L0_ffn", "layer.head"):
        assert f"/{scope}/" in text, scope
    assert "layer.L2_attn.indexer" not in text
    assert "layer.L0_attn.window_latent_attention" not in text
    assert "latent_attention/" not in text.replace(
        "sparse_latent_attention/", "").replace(
            "window_latent_attention/", "")


# ------------------------------------------------------------- the builder
def test_the_builder_reads_this_files_keys(net):
    kinds = [(type(net.vertices[f"L{i}_attn"].layer).__name__,
              net.vertices[f"L{i}_attn"].layer.STATE_KIND) for i in range(5)]
    assert kinds == [("LatentAttention", "sparse_latent")] * 2 + [
        ("LatentAttention", "window_latent")] * 3
    full, window = (net.vertices[n].layer for n in ("L0_attn", "L2_attn"))
    assert (full.n_heads, full.q_rank, full.kv_rank, full.d_nope,
            full.d_rope, full.d_v, full.rope_theta, full.gate,
            full.index_heads, full.index_dim, full.index_rope, full.topk,
            full.window) == (4, 64, 16, 16, 8, 16, 8e7, True, 4, 16, 8,
                             TOPK, None)
    assert (window.n_heads, window.q_rank, window.kv_rank, window.d_nope,
            window.d_rope, window.d_v, window.rope_theta, window.gate,
            window.index_heads, window.window, window.chunk) == (
                2, 64, 24, 24, 8, 16, 5e4, True, 0, WINDOW, CHUNK)
    assert isinstance(net.vertices["L0_ffn"].layer, decoder.GatedFeedForward)
    moe = net.vertices["L1_moe"].layer
    assert (moe.scoring, moe.n_experts, moe.top_k, moe.width, moe.n_shared,
            moe.routed_scaling, moe.held()) == ("sigmoid", 16, 3, 32, 1, 1.0,
                                                HELD)
    assert float(jnp.abs(net.params["L1_moe"]["router_bias"]).sum()) > 0
    assert sorted(net.params["L0_attn"]) == sorted(
        ["Wqa", "q_gain", "Wqb", "Wkva", "kv_gain", "Wkvb", "Wo", "Wg",
         "WqI", "WkI", "kI_gain", "kI_bias", "Ww"])
    assert net.params["L2_attn"]["Wg"].shape == (64, 2)


@pytest.mark.parametrize("change,names", [
    ({"layer_types": ["chunked_attention"] * 9}, r"layer_types\[0\]"),
    ({"attention_gate_type": "elementwise"}, "attention_gate_type"),
    ({"swa_attention_gate_type": "elementwise"}, "swa_attention_gate_type"),
    ({"swa_head_dim": 64}, "swa_head_dim"),
    ({"index_rope_head_dim": 32}, "index_rope_head_dim"),
    ({"swa_v_head_dim": None}, "swa_v_head_dim"),
    ({"index_topk": None}, "index_topk"),
    ({"num_key_value_heads": 2}, "num_key_value_heads"),
    ({"swa_num_key_value_heads": 1}, "swa_num_key_value_heads"),
    ({"rope_scaling": {"factor": 4}}, "rope_scaling"),
    ({"n_group": 8, "topk_group": 4}, "n_group"),
    ({"layer_types": None}, "layer_types"),
])
def test_a_latent_file_the_builder_cannot_make_raises_and_names_the_key(
        change, names):
    """Instead of building another model: a builder that reads none of
    ``layer_types``, ``swa_*``, ``index_*`` or the gates of a file with
    ``kv_lora_rank`` makes every layer a full one."""
    cfg = {k: v for k, v in {**CFG, **change}.items() if v is not None}
    with pytest.raises(ValueError, match=names):
        from_config(cfg, **ARGS)


def test_a_latent_file_without_the_new_keys_builds_what_it_built():
    """Xing4.0 and A.X K1 name none of the new keys: plain latent
    layers, no gate, no window, no indexer."""
    for name in ("xing4_29b_a4b", "ax_k1"):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               f"{name}.json")) as fh:
            cfg = json.load(fh)
        g = ComputationGraph(from_config(cfg, cache_len=64,
                                         **cfg["builder_args"]))
        for n in g._layer_names():
            layer = g.vertices[n].layer
            if isinstance(layer, decoder.LatentAttention):
                assert (layer.STATE_KIND, layer.gate, layer.window,
                        layer.index_heads) == ("latent", False, None, 0)


def test_the_real_file_holds_what_its_sizes_reckon():
    """Every published width, the depth, the experts held and the
    vocabulary cut: 1,822 M parameters in bf16, 3.64 GB."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "dots3_note_prev.json")) as fh:
        cfg = json.load(fh)
    g = ComputationGraph(from_config(cfg, cache_len=32768,
                                     **cfg["builder_args"]))
    count = lambda n: sum(int(np.prod(a.shape)) for a in jax.eval_shape(
        lambda k: g.vertices[n].layer.init_params(k, jnp.bfloat16),
        jax.random.PRNGKey(0)).values())
    total = sum(count(n) for n in g._layer_names())
    assert total == pytest.approx(1822e6, rel=0.02)
    assert total == 1822230016
    c, full, window = 5120, 128, 64
    assert count("L0_attn") == count("L1_attn") == (
        c * 1024 + 1024 + 1024 * full * 192 + c * 576 + 512
        + 512 * full * 256 + full * 128 * c + c * full       # the gate
        + 1024 * 64 * 128 + c * 128 + 2 * 128 + c * 64)      # the indexer
    assert count("L2_attn") == (
        c * 1024 + 1024 + 1024 * window * 256 + c * 1088 + 1024
        + 1024 * window * 320 + window * 128 * c + c * window)
    assert count("L1_moe") == 9 * 3 * 5120 * 1536 + 5120 * 256 + 256
    assert count("embed") == count("head") == 19008 * 5120
    rings = {n: [a.shape for a in g.vertices[n].layer.init_carry(
        32, jnp.bfloat16, 32768)[:-1]] for n in ("L0_attn", "L2_attn")}
    assert rings == {"L0_attn": [(32, 32768, 640), (32, 32768, 128)],
                     "L2_attn": [(32, 640, 1152)]}
