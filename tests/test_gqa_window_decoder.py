"""The decoder whose attention layers are a window's (a ring that wraps)
beside full ones (a ring that grows), under one LayerNorm a block, with
sigmoid-routed experts under a share beside averaged shared experts and
a head tied to the embedding, at a small size on the CPU: the served
path against the plain reference
(``benchmark/reference/gqa_window_moe.py``, which shares no code with
the package) with the prompt several windows long and the window rings
wrapped; planted faults the comparison has to catch; the streamed kernel
(interpreted) against the masked form on both sides of the wrap; the
shares tied to the uncut layer; two kinds of ring in one session cache;
the builder's four kinds of file."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gqa_window_moe as ref
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.models.mla_moe_decoder import from_config
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import decoder
from deeplearning4j_tpu.ops import attention
from deeplearning4j_tpu.serving import InferenceEngine, SessionCache
from deeplearning4j_tpu.serving.sessions import SessionError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW, CHUNK = 8, 7
#: the keys of ``benchmark/configs/command_a_plus_05_2026.json`` at a
#: small size: 4 query heads over 2 key/value heads of 16, a window of 8,
#: one period of three window layers and a full one, 16 sigmoid-routed
#: experts of which this chip holds ids 4-7, 3 a token, 4 shared experts
#: averaged, a tied head
HELD = [4, 5, 6, 7]
CFG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=4,
    intermediate_size=32, num_experts=4, num_experts_per_tok=3,
    num_shared_experts=4, norm_topk_prob=True,
    expert_selection_fn="sigmoid",
    shared_expert_combination_strategy="average", first_k_dense_replace=0,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    layer_norm_eps=1e-5, rms_norm_eps=None, rope_theta=50000,
    sliding_window=WINDOW, layer_switch=4,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    position_embedding_type="rope_gptj", rotary_pct=1,
    use_parallel_block=True, tie_word_embeddings=True, logit_scale=1,
    use_qk_norm=False, attention_bias=False,
    published={"num_experts": 16}, builder_args={"experts_held": HELD})
ARGS = dict(cache_len=512, init_std=0.3, seed=3, experts_held=HELD,
            max_chunk=CHUNK, dtype="float32")
#: a window ring's slots here: 8 + 7 - 1 in whole blocks of 128
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
    """Prefill in chunks of 7 (300 positions: the window rings of 128
    slots wrap twice and chunks straddle the wrap), then 19 token steps:
    ``(kept logits (2 rows, 19, vocabulary), the whole sequence)``."""
    cache = SessionCache(net, name="served", ttl_s=0.0)
    cache.prefill("s", ids[:, :280], chunk=CHUNK, cache_len=512)
    gen = cache.generate("s", ids[:, 280:281], 19)
    kept = np.stack([np.asarray(k) for k in gen.kept_logits], axis=1)
    return kept, np.concatenate([ids[:, :281], gen.ids[:, :-1]], axis=1)


# ------------------------------------------------ program and reference
def test_prefill_then_token_steps_equal_the_references_full_forward(
        net, served):
    kept, sequence = served
    assert sequence.shape[1] == 299 > 2 * SLOTS > 30 * WINDOW
    want = np.asarray(ref.forward(CFG, net.params, sequence, last=19))
    assert rel(kept[0], want[0]) < 1e-5 and rel(kept[1], want[1]) < 1e-5
    # every generated position on its own
    assert max(rel(k, w) for k, w in zip(kept[0], want[0])) < 1e-4


def test_output_agrees_with_the_reference(net, ids):
    assert rel(net.output(ids[:, :40]),
               ref.forward(CFG, net.params, ids[:, :40])) < 1e-5


@pytest.mark.parametrize("fault", [
    {"faults": ("window_plus_one",)}, {"rope_all": True},
    {"faults": ("shared_sum",)}, {"faults": ("second_norm",)},
    {"faults": ("untied_head",)}, {"all_full": True},
    {"fp8_weights": True}])
def test_a_planted_fault_fails_the_comparison(net, served, fault):
    """A window of ``W + 1``, rotary on the full layer, the shared
    experts summed, a second norm, an untied head, no window at all,
    float8 weights: each reads far over the float32 limit."""
    kept, sequence = served
    wrong = np.asarray(ref.forward(CFG, net.params, sequence, last=19,
                                   **fault))
    assert rel(kept[0], wrong[0]) > 0.05, fault


def test_the_reference_takes_any_query_block(net, ids):
    want = ref.forward(CFG, net.params, ids[:, :61])
    for block in (5, 16, 61, 200):
        assert rel(ref.forward(CFG, net.params, ids[:, :61],
                               query_block=block), want) < 1e-5, block


# ----------------------------------------------------------- the ring
def test_a_window_rings_slots():
    assert attention.window_ring_slots(4096, 256) == 4608
    assert attention.window_ring_slots(8, 7) == 128
    assert attention.window_ring_slots(128, 1) == 128
    assert attention.window_ring_slots(128, 2) == 256


@pytest.mark.parametrize("cursor,t", [(0, 5), (9, 1), (10, 5), (13, 3),
                                      (40, 4), (57, 1)])
def test_visibility_by_hand(cursor, t):
    """15 slots for a window of 8 under chunks of up to 8; slot ``c``
    holds the newest written position congruent to ``c``."""
    cap, window = 15, 8
    last = cursor + t - 1
    held = [max(p for p in range(-cap, last + 1) if p % cap == c)
            for c in range(cap)]
    for w in (None, window):
        want = [[0 <= held[c] <= cursor + i
                 and (w is None or held[c] > cursor + i - w)
                 for c in range(cap)] for i in range(t)]
        np.testing.assert_array_equal(
            attention.ring_visible(cursor, t, cap, w), want)


def test_a_chunk_that_straddles_the_end_is_written_around_it():
    ring = jnp.zeros((2, 12, 2, 4))
    k = jnp.arange(2 * 5 * 4, dtype=jnp.float32).reshape(2, 5, 4) + 1
    out = attention.gqa_ring_update(ring, 10, k, -k, wraps=True)
    for j, slot in enumerate((10, 11, 0, 1, 2)):
        np.testing.assert_array_equal(out[:, slot, 0], k[:, j])
        np.testing.assert_array_equal(out[:, slot, 1], -k[:, j])
    assert float(jnp.abs(out[:, 3:10]).sum()) == 0
    one = attention.gqa_ring_update(ring, 25, k[:, :1], k[:, :1], wraps=True)
    np.testing.assert_array_equal(one[:, 1, 0], k[:, 0])
    # a ring that grows is written where the cursor says
    flat = attention.gqa_ring_update(ring, 7, k, k)
    np.testing.assert_array_equal(flat[:, 7:12, 0], k)


def _ring_after(positions, cap, kv_heads, d, wraps, seed=0):
    """A ring written position by position up to ``positions``, and the
    keys and values it was written from."""
    rng = np.random.RandomState(seed)
    k = rng.randn(2, positions, kv_heads * d).astype(np.float32)
    v = rng.randn(2, positions, kv_heads * d).astype(np.float32)
    ring = jnp.zeros((2, cap, 2 * kv_heads, d), jnp.float32)
    for p in range(positions):
        ring = attention.gqa_ring_update(ring, p, k[:, p:p + 1],
                                         v[:, p:p + 1], wraps=wraps)
    return ring, k, v


def _by_hand(q, k, v, cursor, window):
    """Softmax attention of each query over the positions it sees, a
    loop over rows, positions and heads."""
    b, t, h, d = q.shape
    g = k.shape[-1] // d
    k, v = (a.reshape(b, -1, g, d) for a in (k, v))
    out = np.zeros(q.shape)
    for row in range(b):
        for i in range(t):
            at = cursor + i
            lo = 0 if window is None else max(0, at - window + 1)
            for head in range(h):
                keys = k[row, lo:at + 1, head // (h // g)]
                s = keys @ q[row, i, head] / np.sqrt(d)
                p = np.exp(s - s.max())
                out[row, i, head] = (p / p.sum()) @ v[
                    row, lo:at + 1, head // (h // g)]
    return out


@pytest.mark.parametrize("window,cursor,t", [
    (None, 0, 8), (None, 37, 1), (None, 40, 8), (None, 50, 5),
    (24, 3, 1), (24, 20, 8), (24, 60, 1),       # before the wrap
    (24, 60, 8), (24, 63, 1),                   # at it: a chunk straddles
    (24, 64, 1), (24, 100, 16), (24, 150, 1), (24, 190, 3)])  # and after
def test_the_streamed_kernel_agrees_with_the_masked_form(window, cursor, t):
    """Interpreted, blocks of 16 slots in a ring of 64: with a window of
    24 a call sees at most 3 or 4 blocks, so blocks are skipped before
    the window and beyond the cursor, also where the walk wraps."""
    cap, h, g, d = 64, 4, 2, 16
    ring, k, v = _ring_after(cursor + t, cap, g, d, wraps=window is not None)
    q = np.random.RandomState(1).randn(2, t, h, d).astype(np.float32)
    want = _by_hand(q, k, v, cursor, window)
    masked = attention.gqa_ring_attention_masked(
        jnp.asarray(q), ring, cursor, sm_scale=d ** -0.5, window=window)
    assert rel(masked, want) < 1e-5
    padded = jnp.pad(jnp.asarray(q), [(0, 0), (0, -t % 8 if t > 1 else 0),
                                      (0, 0), (0, 0)])
    streamed = attention.gqa_ring_attention_streamed(
        padded, ring, cursor, sm_scale=d ** -0.5, window=window, written=t,
        block=16, interpret=True)[:, :t]
    assert rel(streamed, want) < 1e-5


def test_the_kernel_walks_only_the_blocks_that_hold_a_visible_position(
        monkeypatch):
    """What the walk fetches: a NaN in any block it should skip would
    poison the result; the blocks before the window and beyond the
    cursor are filled with NaN and the answer stays."""
    cap, h, g, d, window, cursor = 64, 4, 2, 16, 24, 100
    ring, k, v = _ring_after(cursor + 1, cap, g, d, wraps=True)
    # positions 77..100 are seen: slots 13..36, blocks 0, 1, 2 of 16
    poisoned = ring.at[:, 48:].set(jnp.nan)
    q = jnp.asarray(np.random.RandomState(2).randn(2, 1, h, d), jnp.float32)
    got = attention.gqa_ring_attention_streamed(
        q, poisoned, cursor, sm_scale=d ** -0.5, window=window, block=16,
        interpret=True)
    assert bool(jnp.isfinite(got).all())
    assert rel(got, _by_hand(np.asarray(q), k, v, cursor, window)) < 1e-5


def test_the_path_is_chosen_from_the_shapes(monkeypatch):
    path = attention.gqa_attention_path
    args = dict(heads=128, kv_heads=8, d=128, capacity=4608,
                dtype=jnp.bfloat16)
    assert path(1, **args) == "masked"          # no Mosaic here
    monkeypatch.setattr(attention, "_mosaic", lambda: True)
    assert path(1, **args) == path(256, **args) == "streamed"
    assert path(1, **{**args, "capacity": 32768}) == "streamed"
    assert path(4608, **args) == "masked"       # output() from a zero ring
    assert path(1, **{**args, "d": 64}) == "masked"
    assert path(1, **{**args, "dtype": jnp.float64}) == "masked"
    assert path(1, **{**args, "capacity": 4351}) == "masked"
    assert path(1, **{**args, "heads": 12}) == "masked"
    layer = decoder.GroupedQueryAttention(
        n_in=64, n_out=64, n_heads=4, n_kv_heads=2, head_dim=128, window=8,
        chunk=7)
    assert layer.attention_path(1, layer.init_carry(2, jnp.bfloat16)) \
        == "streamed"


# ------------------------------------------------------------ the layers
def test_layer_norm_has_a_gain_and_no_bias():
    layer = decoder.LayerNorm(n_out=6, eps=1e-5)
    p = layer.init_params(jax.random.PRNGKey(0))
    assert list(p) == ["gain"] and layer.param_order() == ("gain",)
    x = np.random.RandomState(0).randn(2, 3, 6).astype(np.float32) + 4
    gain = np.arange(1, 7, dtype=np.float32)
    y = layer.forward({"gain": jnp.asarray(gain)}, {}, jnp.asarray(x),
                      train=False)[0]
    centred = x - x.mean(-1, keepdims=True)
    want = centred / np.sqrt((centred ** 2).mean(-1, keepdims=True) + 1e-5)
    assert rel(y, want * gain) < 1e-6


def test_shared_experts_combine_by_their_average(net):
    layer, p = net.vertices["L1_moe"].layer, net.params["L1_moe"]
    assert (layer.n_shared, layer.shared_combine, layer.scoring) == (
        4, "average", "sigmoid")
    assert p["Sg"].shape == (64, 4 * 32) and p["Sd"].shape == (4 * 32, 64)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 5, 64), jnp.float32)
    y = layer.forward(p, layer.init_state(), x, train=False)[0]
    assert rel(y, ref.moe(CFG, p, x, HELD)) < 1e-5
    summed = decoder.MixtureOfExperts(**{
        **{f.name: getattr(layer, f.name)
           for f in layer.__dataclass_fields__.values()},
        "shared_combine": "sum"})
    # the average is the sum with a quarter of the last product
    quarter = {**p, "Sd": p["Sd"] / 4}
    assert rel(summed.forward(quarter, layer.init_state(), x,
                              train=False)[0], y) < 1e-6
    assert rel(summed.forward(p, layer.init_state(), x, train=False)[0],
               y) > 0.1
    with pytest.raises(ValueError, match="sum.*average"):
        decoder.MixtureOfExperts(**{
            **{f.name: getattr(layer, f.name)
               for f in layer.__dataclass_fields__.values()},
            "shared_combine": "max"}).forward(
                p, layer.init_state(), x, train=False)


def test_the_shares_of_all_holders_add_up_to_the_uncut_layer():
    """Four chips hold ids 0-3, 4-7, 8-11, 12-15 of a router of 16: the
    shares' routed parts, with the averaged shared experts (which every
    chip computes alike) counted once, are the uncut reference's expert
    layer."""
    x = jnp.asarray(np.random.RandomState(4).randn(2, 9, 64), jnp.float32)
    cut = lambda held: {**CFG, "num_experts": len(held),
                        "builder_args": {"experts_held": held}}
    whole_cfg = {**CFG, "num_experts": 16, "published": {}}
    whole = build(whole_cfg, experts_held=None)
    want = ref.moe(whole_cfg, whole.params["L1_moe"], x)
    sg, su, sd = (np.asarray(whole.params["L1_moe"][k], np.float64)
                  for k in ("Sg", "Su", "Sd"))
    flat = np.asarray(x, np.float64)
    gate = flat @ sg
    shared_once = ((gate / (1 + np.exp(-gate))) * (flat @ su)) @ sd / 4
    total = 0
    for held in ([0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11],
                 [12, 13, 14, 15]):
        share = build(cut(held), experts_held=held)
        layer, p = share.vertices["L1_moe"].layer, share.params["L1_moe"]
        for j, e in enumerate(held):        # the same draws as the whole
            np.testing.assert_array_equal(
                p["Wg"][:, j * 32:(j + 1) * 32],
                whole.params["L1_moe"]["Wg"][:, e * 32:(e + 1) * 32])
        np.testing.assert_array_equal(p["Sg"], whole.params["L1_moe"]["Sg"])
        y = layer.forward(p, layer.init_state(), x, train=False)[0]
        assert rel(y, ref.moe(cut(held), p, x, held)) < 1e-5
        total = total + (y - shared_once)
    assert rel(total + shared_once, want) < 1e-5


def test_the_head_reads_the_embeddings_table(net, ids):
    assert net.params["head"] == {} and "W" in net.params["embed"]
    head = net.vertices["head"].layer
    assert isinstance(head, decoder.TiedLMHead) and head.tied_to == "embed"
    # the table is held once: 256 x 64 and no second matrix
    assert net.num_params() == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(net.params))
    before = np.asarray(net.output(ids[:, :9]))
    # a new table is the head's at the next call: no copy to fall behind
    other = build()
    table = np.asarray(other.params["embed"]["W"])
    other.params = {**other.params,
                    "embed": {"W": jnp.asarray(2.0 * table)}}
    after = np.asarray(other.output(ids[:, :9]))
    want = np.asarray(ref.forward(CFG, other.params, ids[:, :9]))
    assert rel(after, want) < 1e-5 and rel(after, before) > 0.1
    # a gradient reaches the table from the embedding and from the head
    def loss(table):
        params = {**net.params, "embed": {"W": table}}
        acts = net._forward(params, net.net_state, (ids[:, :5],),
                            train=False, rng=None)[0]
        return jnp.sum(acts["head"][:, -1, 7])
    grad = np.asarray(jax.grad(loss)(net.params["embed"]["W"]))
    unused = np.setdiff1d(np.arange(256), np.append(ids[:, :5].ravel(), 7))
    assert np.abs(grad[7]).sum() > 0                    # the head's row
    assert np.abs(grad[ids[0, 0]]).sum() > 0            # an embedded id's
    assert np.abs(grad[unused]).sum() == 0


def test_logit_scale_multiplies_the_logits(ids):
    scaled = build({**CFG, "logit_scale": 0.25})
    plain = build()
    assert rel(scaled.output(ids[:, :6]),
               0.25 * np.asarray(plain.output(ids[:, :6]))) < 1e-6
    assert rel(scaled.output(ids[:, :6]), ref.forward(
        {**CFG, "logit_scale": 0.25}, scaled.params, ids[:, :6])) < 1e-5


# --------------------------------------------------------------- sessions
def test_two_kinds_of_ring_in_one_cache(net, ids):
    """Window rings at their own size beside a full ring at the
    session's: bytes by kind, a fork that leaves the snapshot intact, a
    position far past the window rings' capacity."""
    window, full = (net.vertices[n].layer for n in ("L0_attn", "L3_attn"))
    assert (window.STATE_KIND, full.STATE_KIND) == ("window_kv", "kv")
    assert (window.RING_GROWS, full.RING_GROWS) == (False, True)
    # a window layer's ring is its own size whatever it is handed
    for asked in (None, 16, 4096):
        ring, cursor = window.init_carry(3, jnp.float32, asked)
        assert ring.shape == (3, SLOTS, 4, 16) and cursor.shape == ()
    assert window.grow_carry((ring, cursor), 4096)[0] is ring
    assert full.init_carry(3, jnp.float32, 48)[0].shape == (3, 48, 4, 16)
    assert full.grow_carry(full.init_carry(3, jnp.float32, 48), 96)[
        0].shape == (3, 96, 4, 16)
    with pytest.raises(ValueError, match="shrink"):
        full.grow_carry(full.init_carry(3, jnp.float32, 48), 32)
    assert net.max_cache_len() == 512           # the rings that grow
    with InferenceEngine(net, max_batch_size=2, name="two") as engine:
        cache = engine.sessions
        engine.prefill_session("snap", ids[:, :280], chunk=CHUNK,
                               cache_len=320)
        held = jax.tree.map(np.asarray, cache.get_carries("snap"))
        assert {n: c[0].shape[1] for n, c in held.items()} == {
            "L0_attn": SLOTS, "L1_attn": SLOTS, "L2_attn": SLOTS,
            "L3_attn": 320}
        assert cache.session_position("snap") == 280 > 2 * SLOTS
        assert cache.session_capacity("snap") == 320
        engine.fork_session("snap", "a")
        a = engine.generate("a", ids[:, 280:281], 6)
        engine.fork_session("snap", "b")
        b = engine.generate("b", ids[:, 280:281], 6)
        np.testing.assert_array_equal(a.ids, b.ids)
        for kept, now in zip(jax.tree.leaves(held), jax.tree.leaves(
                jax.tree.map(np.asarray, cache.get_carries("snap")))):
            np.testing.assert_array_equal(kept, now)
        value = lambda kind: monitor.gauge(
            "serving_session_state_bytes", "").value(model="two", kind=kind)
        slot = 2 * 2 * 16 * 4               # 2 + 2 heads of 16, float32
        assert value("window_kv") == 3 * (3 * (2 * SLOTS * slot) + 3 * 4)
        assert value("kv") == 3 * (2 * 320 * slot + 4)
        assert cache.state_bytes() == value("window_kv") + value("kv")
        # the full ring is what a prompt has to fit
        with pytest.raises(SessionError, match="do not fit"):
            engine.generate("a", a.ids[:, -1:], 40)
    kept = np.stack([np.asarray(k) for k in a.kept_logits], axis=1)
    sequence = np.concatenate([ids[:, :281], a.ids[:, :-1]], axis=1)
    assert rel(kept, ref.forward(CFG, net.params, sequence, last=6)) < 1e-5


def test_a_ladder_hop_grows_only_the_full_rings(net, ids):
    cache = SessionCache(net, name="hop", ttl_s=0.0)
    want = np.asarray(net.output(ids[:, :40]))
    steps = [cache.step("s", ids[:, t:t + 1]) for t in range(40)]
    assert rel(np.stack(steps, axis=1), want) < 1e-5
    assert cache.session_capacity("s") == 64    # 1, 2, 4, ... hopped to
    shapes = {n: c[0].shape[1] for n, c in cache.get_carries("s").items()}
    assert shapes == {"L0_attn": SLOTS, "L1_attn": SLOTS, "L2_attn": SLOTS,
                      "L3_attn": 64}


def test_window_rings_alone_have_no_ladder_and_no_limit(ids):
    cfg = {**CFG, "num_hidden_layers": 2}
    alone = build(cfg)
    assert alone.max_cache_len() == 0 and alone.has_kv_ring()
    cache = SessionCache(alone, name="alone", ttl_s=0.0)
    assert cache._cache_ladder == ()
    cache.prefill("s", ids[:, :290], chunk=CHUNK)
    gen = cache.generate("s", ids[:, 290:291], 5)
    assert cache.session_position("s") == 295 > 2 * SLOTS
    assert cache.session_capacity("s") == 0
    kept = np.stack([np.asarray(k) for k in gen.kept_logits], axis=1)
    sequence = np.concatenate([ids[:, :291], gen.ids[:, :-1]], axis=1)
    assert rel(kept, ref.forward(cfg, alone.params, sequence,
                                 last=5)) < 1e-5


def test_a_chunk_longer_than_the_ring_was_sized_for_is_refused(net):
    layer = net.vertices["L0_attn"].layer
    x = jnp.zeros((1, SLOTS - WINDOW + 2, 64))
    with pytest.raises(ValueError, match="under a window of 8"):
        layer.forward_seq(net.params["L0_attn"], x,
                          layer.init_carry(1, jnp.float32), train=False)


def test_steps_are_counted_by_kind_and_form(net, ids):
    count = lambda **labels: monitor.counter(
        "gqa_attention_steps_total", "").value(**labels)
    before = {kind: count(kind=kind, path="masked")
              for kind in ("kv", "window_kv")}
    cache = SessionCache(net, name="counted", ttl_s=0.0)
    cache.prefill("s", ids[:, :21], chunk=CHUNK, cache_len=64)
    cache.generate("s", ids[:, 21:22], 4)
    for kind in ("kv", "window_kv"):    # 3 chunks and 4 token steps
        assert count(kind=kind, path="masked") - before[kind] == 7


# ------------------------------------------------------------- the builder
def test_the_builder_reads_this_files_keys(net):
    assert list(net.vertices) == [
        "embed",
        *(f"L{i}_{part}" for i in range(4)
          for part in ("norm", "attn", "moe", "add")),
        "final_norm", "head"]
    # one norm a layer feeds both sublayers, and the block adds three
    inputs = lambda name: list(net.vertices[name].inputs)
    assert inputs("L2_attn") == ["L2_norm"] == inputs("L2_moe")
    assert inputs("L2_add") == ["L1_add", "L2_attn", "L2_moe"]
    assert inputs("L0_add")[0] == "embed" == inputs("L0_norm")[0]
    kinds = [(net.vertices[f"L{i}_attn"].layer.window,
              net.vertices[f"L{i}_attn"].layer.rotary) for i in range(4)]
    assert kinds == [(WINDOW, True)] * 3 + [(None, False)]
    attn = net.vertices["L0_attn"].layer
    assert isinstance(attn, decoder.GroupedQueryAttention)
    assert (attn.n_heads, attn.n_kv_heads, attn.head_dim, attn.rope_theta,
            attn.chunk, attn.cache_len) == (4, 2, 16, 50000.0, CHUNK, 512)
    assert sorted(net.params["L0_attn"]) == ["Wk", "Wo", "Wq", "Wv"]
    norm = net.vertices["L0_norm"].layer
    assert isinstance(norm, decoder.LayerNorm) and norm.eps == 1e-5
    assert isinstance(net.vertices["final_norm"].layer, decoder.LayerNorm)
    moe = net.vertices["L3_moe"].layer
    # ``num_experts`` does not mean softmax: the file names its scoring
    assert (moe.scoring, moe.n_experts, moe.top_k, moe.width, moe.n_shared,
            moe.shared_combine, moe.routed_scaling, moe.held()) == (
                "sigmoid", 16, 3, 32, 4, "average", 1.0, HELD)
    assert float(jnp.abs(net.params["L3_moe"]["router_bias"]).sum()) == 0


@pytest.mark.parametrize("change,names", [
    ({"use_qk_norm": True}, "use_qk_norm"),
    ({"attention_bias": True}, "attention_bias"),
    ({"position_embedding_type": "rope_neox"}, "position_embedding_type"),
    ({"rotary_pct": 0.5}, "rotary_pct"),
    ({"layer_types": ["chunked_attention"] * 4}, r"layer_types\[0\]"),
    ({"shared_expert_combination_strategy": "concat"},
     "shared_expert_combination_strategy"),
])
def test_a_file_the_builder_cannot_read_raises_and_names_the_key(change,
                                                                 names):
    with pytest.raises(ValueError, match=names):
        from_config({**CFG, **change}, **ARGS)


def test_a_file_with_no_attention_it_makes_says_which_it_makes():
    cfg = {k: v for k, v in CFG.items() if k != "layer_types"}
    with pytest.raises(ValueError, match="layer_types"):
        from_config(cfg, **ARGS)


def _shape_digest(cfg, **kw):
    """Vertex names and parameter shapes of the graph the builder makes
    of ``cfg``, nothing drawn."""
    g = ComputationGraph(from_config(cfg, **kw))
    key = jax.random.PRNGKey(0)
    shapes = {n: {k: tuple(a.shape) for k, a in jax.eval_shape(
        lambda k, n=n: g.vertices[n].layer.init_params(k, jnp.bfloat16),
        key).items()} for n in g._layer_names()}
    text = json.dumps([list(g.vertices), shapes], sort_keys=True)
    return (hashlib.sha256(text.encode()).hexdigest()[:16],
            sum(int(np.prod(s)) for v in shapes.values() for s in v.values()),
            len(g.vertices))


@pytest.mark.parametrize("name,digest,parameters,vertices", [
    ("xing4_29b_a4b", "51e1c250b7c5eb31", 4792669828, 53),
    ("ax_k1", "be9dbf2e8a9653ae", 4166295488, 39),
    ("keye_vl2_30b_a3b", "fe0bd802d0591ca8", 659190784, 39),
    ("command_a_plus_05_2026", "b0996b835569fa6c", 4733293056, 19),
])
def test_the_benchmarks_files_build_the_graphs_they_built(
        name, digest, parameters, vertices):
    """Vertex names and the shapes of every parameter from the
    benchmark's own files: the first three digests were taken from the
    parent commit's builder, before it read ``layer_types``."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{name}.json")) as fh:
        cfg = json.load(fh)
    got = _shape_digest(cfg, cache_len=64, **cfg["builder_args"])
    assert got == (digest, parameters, vertices)


def test_the_real_file_holds_what_the_issue_reckoned():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "command_a_plus_05_2026.json")) as fh:
        cfg = json.load(fh)
    g = ComputationGraph(from_config(cfg, cache_len=32768,
                                     **cfg["builder_args"]))
    count = lambda n: sum(int(np.prod(a.shape)) for a in jax.eval_shape(
        lambda k: g.vertices[n].layer.init_params(k, jnp.bfloat16),
        jax.random.PRNGKey(0)).values())
    assert count("L0_attn") == 4096 * 16384 * 2 + 2 * 4096 * 1024
    assert count("L0_moe") == (16 + 4) * 3 * 4096 * 4096 + 4096 * 128 + 128
    assert count("embed") == 32768 * 4096 and count("head") == 0
    rings = {n: g.vertices[n].layer.init_carry(8, jnp.bfloat16, 32768)[
        0].shape for n in ("L0_attn", "L2_attn", "L3_attn")}
    assert rings == {"L0_attn": (8, 4608, 16, 128),
                     "L2_attn": (8, 4608, 16, 128),
                     "L3_attn": (8, 32768, 16, 128)}
    # 1.42 GiB a copy, of which the window rings are 29.7%
    slot = 16 * 128 * 2
    assert 8 * (32768 + 3 * 4608) * slot / 2 ** 30 == pytest.approx(
        1.422, abs=0.001)
    assert 3 * 4608 / (32768 + 3 * 4608) == pytest.approx(0.297, abs=0.001)


# --------------------------------------------------------------- the scopes
def test_the_two_attentions_have_scopes_of_their_own(net):
    text = net._token_step_fn.lower(
        net.params, net.net_state, net._init_carries(2, cache_len=64),
        jnp.zeros((2, 1), jnp.int32), net.zero_expert_counts()).as_text(
            debug_info=True)
    for scope in ("layer.L0_attn.window_attention",
                  "layer.L3_attn.full_attention", "layer.L1_moe.router",
                  "layer.L1_moe.experts", "layer.L1_moe.shared",
                  "layer.L2_norm", "layer.L2_add", "layer.head"):
        assert f"/{scope}/" in text, scope
    assert "layer.L3_attn.window_attention" not in text
    assert "layer.L0_attn.full_attention" not in text
    assert monitor.parse_op_name(
        "jit(run)/layer.L0_attn/layer.L0_attn.window_attention/"
        "pallas_call") == ("layer.L0_attn.window_attention", "forward")
