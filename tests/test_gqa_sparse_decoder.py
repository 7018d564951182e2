"""The decoder of grouped-query attention over the rows a learned indexer
selects, with softmax-routed experts under a share, at a small size on
the CPU: the layer against the plain reference
(``benchmark/reference/gqa_sparse_moe.py``, which shares no code with
the package) for contexts under, at and over ``topk``; the three forms
of the op against each other and the sets they pick; the router's two
scorings; the shares tied to the uncut layer; the builder's three kinds
of file; the three-ring state through the session cache, alone and
beside latent rings; the scopes a trace tells apart."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gqa_sparse_moe as ref
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.models.mla_moe_decoder import from_config
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.conf.neural_net_configuration import \
    NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import decoder
from deeplearning4j_tpu.nn.weights import Distribution
from deeplearning4j_tpu.ops import attention
from deeplearning4j_tpu.serving import InferenceEngine, SessionCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOPK = 16
#: the keys of ``benchmark/configs/keye_vl2_30b_a3b.json`` at a small
#: size: 4 query heads over 2 key/value heads of 16, an indexer of 3
#: heads of 8 that picks 16 rows, 8 softmax-routed experts of which this
#: chip holds ids 4-7, 2 a token
HELD = [4, 5, 6, 7]
CFG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=2,
    intermediate_size=160, moe_intermediate_size=32, num_experts=4,
    num_local_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[], num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
    rope_theta=10000000,
    rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default",
                  "type": "default"},
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 3,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": TOPK},
    published={"num_experts": 8}, builder_args={"experts_held": HELD})
ARGS = dict(cache_len=64, init_std=0.3, seed=3, experts_held=HELD)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def joined(k, v, d):
    """(batch, slots, kv heads x d) keys and values as the layer's ring,
    (batch, slots, 2 x kv heads, d): the key heads, then the value
    heads."""
    by_head = lambda a: a.reshape(a.shape[:2] + (-1, d))
    return jnp.concatenate([by_head(k), by_head(v)], axis=2)


def build(cfg=CFG, **kw):
    return ComputationGraph(from_config(cfg, **{**ARGS, **kw})).init()


@pytest.fixture(scope="module")
def net():
    return build()


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(0).randint(0, 256, (3, 40)).astype(np.int32)


def acts(shape, seed=1):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.float32)


# ------------------------------------------------- the layer, the reference
@pytest.mark.parametrize("t", [9, TOPK, 40])
def test_attention_agrees_with_the_reference(net, t):
    """Full-sequence forward from a zero ring, contexts under, at and
    over ``topk``."""
    layer, p = net.vertices["L1_attn"].layer, net.params["L1_attn"]
    x = acts((2, t, 64))
    got = layer.forward(p, {}, x, train=False)[0]
    want = ref.attention(CFG, p, x)
    assert rel(got, want) < 1e-5
    dense = ref.attention(CFG, p, x, dense_attention=True)
    # the selection is the identity up to topk and decides beyond it
    assert (rel(dense, want) < 1e-6) == (t <= TOPK)


def test_within_topk_it_is_dense_grouped_query_attention(net):
    """With ``topk`` no less than the context: plain causal attention,
    and ``kv_ring_attention`` with the key/value heads repeated."""
    layer = net.vertices["L0_attn"].layer
    b, t, cap = 2, 12, 16                     # the ring no longer than topk
    q, k, v = acts((b, t, 4, 16)), acts((b, cap, 32), 2), acts((b, cap, 32), 3)
    idx = (acts((b, t, 3, 8), 4), acts((b, t, 3), 5), acts((b, cap, 8), 6))
    got, tied = attention.sparse_ring_attention(
        q, idx[0], idx[1], joined(k, v, 16), idx[2], 4, topk=TOPK,
        sm_scale=0.25)
    assert int(tied) == 0
    by_head = lambda ring: jnp.repeat(jnp.transpose(
        ring.reshape(b, cap, 2, 16), (0, 2, 1, 3)), 2, axis=1)
    want = attention.kv_ring_attention(q, by_head(k), by_head(v), 4,
                                       sm_scale=0.25)
    assert rel(got, want) < 1e-6
    assert layer.attention_path(t, layer.init_carry(b, jnp.float32, cap)) \
        == "masked"


@pytest.mark.parametrize("cursor", [0, 5, 23, 48])
@pytest.mark.parametrize("t", [1, 8])
def test_the_two_forms_agree_and_pick_the_same_sets(t, cursor):
    b, cap = 2, 64
    rng = np.random.RandomState(cursor + t)
    draw = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    q, k, v = draw(b, t, 4, 16), draw(b, cap, 32), draw(b, cap, 32)
    q_idx, w_idx, i_ring = draw(b, t, 3, 8), draw(b, t, 3), draw(b, cap, 8)
    # past the newest position: large and finite, to show if it is read
    k = k.at[:, cursor + t:].multiply(1e4)
    scores = attention.indexer_scores(q_idx, w_idx, i_ring)
    streamed = attention.indexer_scores_streamed(q_idx, w_idx, i_ring,
                                                 cursor, block=16)
    visible = attention.visible_slots(cursor, t, cap)[None]
    assert float(jnp.max(jnp.abs(jnp.where(visible, scores - streamed,
                                           0.0)))) < 1e-5
    selected = attention.select_mask(scores, visible, TOPK)
    np.testing.assert_array_equal(
        np.asarray(attention.select_mask_streamed(scores, cursor, TOPK),
                   np.float32) != 0, selected)
    newest = cursor + np.arange(t)
    assert (np.asarray(selected.sum(-1)) == np.minimum(TOPK, newest + 1)).all()
    np.testing.assert_array_equal(
        np.stack([ref.select(scores[i], visible[0], TOPK)
                  for i in range(b)]), selected)
    ring = joined(k, v, 16)
    masked = attention.sparse_attention_masked(q, ring, selected,
                                               sm_scale=0.25)
    assert rel(attention.sparse_attention_streamed(
        q, ring, selected, cursor, sm_scale=0.25, block=16), masked) < 1e-6
    if t == 1:
        # the token step's third form: the selection as slot numbers,
        # made from the scores in one kernel, the slots fetched by the
        # kernel's own descriptors
        slots, count, tied = attention.selected_slots_streamed(
            scores[:, 0], cursor, TOPK)
        # the indexer's exact zeros (every relu 0) may straddle the place
        keys = -np.sort(-np.asarray(scores[:, 0, :cursor + 1]), axis=1)
        assert int(tied[0]) == (cursor + 1 > TOPK and bool(
            (keys[:, TOPK - 1] == keys[:, TOPK]).any()))
        for i in range(b):
            np.testing.assert_array_equal(
                np.asarray(slots[i, :int(count[i])]),
                np.nonzero(np.asarray(selected[i, 0]))[0])
        assert rel(attention.sparse_attention_gathered(
            q, ring, slots, count, sm_scale=0.25), masked) < 1e-6


def _gather_case(case):
    """Scores (2, 1, 256), the cursor and the slots a row has to name,
    for a ring of 256 slots of which 16 are selected."""
    cap, rng = 256, np.random.RandomState(7)
    scores = rng.randn(2, 1, cap).astype(np.float32)
    if case == "few_visible":       # 6 visible: every one, a short list
        return scores, 5, [np.arange(6)] * 2
    if case == "odd_newest":
        # the newest slot is odd and scores highest: the slot after it,
        # its tile's neighbour, is a row nobody wrote
        scores[:, 0, 201] = 50.0
        cursor = 201
    elif case == "neighbours":      # both slots of a pair, and a run of 4
        scores[:, 0, [10, 11, 128, 129, 130, 131]] = 40.0
        cursor = 250
    elif case == "ties":
        # 21 equal scores for the last 6 places: the lowest positions
        scores[:, 0, 3:66:3] = 9.0
        scores[:, 0, [100, 101, 102, 200, 201, 202, 203, 204, 205, 206]] = 20.0
        cursor = 255
    elif case == "last_group":      # every pick in the ring's last 128
        scores[:, 0, 240:256] = 30.0
        cursor = 255
    visible = np.arange(cap) <= cursor
    want = [np.sort(np.argsort(-np.where(visible, s[0], -np.inf),
                               kind="stable")[:TOPK]) for s in scores]
    return scores, cursor, want


@pytest.mark.parametrize("case", ["few_visible", "odd_newest", "neighbours",
                                  "ties", "last_group"])
def test_the_gathered_form_fetches_the_rows_the_selection_named(case):
    """The token step over a long ring: the mask as slot numbers (rising,
    ``count`` of them, the rest slot 0), and the kernel that fetches the
    named slots (through ``pltpu.InterpretParams()``, whose landing
    buffer starts as NaN: a row that was not fetched would show)
    against the mask over dense attention and over the streamed
    kernel."""
    b, cap, d = 2, 256, 128
    scores, cursor, want = _gather_case(case)
    rng = np.random.RandomState(11)
    draw = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    q, k, v = draw(b, 1, 4, d), draw(b, cap, 2 * d), draw(b, cap, 2 * d)
    # past the newest position: rows nobody wrote (large, to show)
    k = k.at[:, cursor + 1:].multiply(1e4)
    ring = joined(k, v, d)
    selected = attention.select_mask_streamed(jnp.asarray(scores), cursor,
                                              TOPK)
    np.testing.assert_array_equal(
        np.asarray(selected, np.float32) != 0,
        attention.select_mask(jnp.asarray(scores),
                              attention.visible_slots(cursor, 1, cap)[None],
                              TOPK))
    slots, count, tied = attention.selected_slots_streamed(
        jnp.asarray(scores[:, 0]), cursor, TOPK)
    assert int(tied[0]) == (case == "ties")
    for i in range(b):
        n = int(count[i])
        np.testing.assert_array_equal(np.asarray(slots[i, :n]), want[i])
        assert not np.asarray(slots[i, n:]).any()
    masked = attention.sparse_attention_masked(q, ring, selected,
                                               sm_scale=d ** -0.5)
    gathered = attention.sparse_attention_gathered(q, ring, slots, count,
                                                   sm_scale=d ** -0.5)
    assert np.isfinite(np.asarray(gathered)).all()
    assert rel(gathered, masked) < 1e-6
    assert rel(gathered, attention.sparse_attention_streamed(
        q, ring, selected, cursor, sm_scale=d ** -0.5, block=128)) < 1e-6
    # bf16 rows as stored: the same rows, the same rounding of p
    as_bf16 = lambda a: a.astype(jnp.bfloat16)
    assert rel(attention.sparse_attention_gathered(
        as_bf16(q), as_bf16(ring), slots, count, sm_scale=d ** -0.5),
        attention.sparse_attention_streamed(
            as_bf16(q), as_bf16(ring), selected, cursor, sm_scale=d ** -0.5,
            block=128)) < 8e-3


def test_an_unselected_neighbour_weighs_nothing():
    """NaN in every row the selection did not name: the gathered form
    never reads them (the masked form would spread them, 0 x NaN)."""
    b, cap, d = 1, 256, 128
    scores, cursor, want = _gather_case("neighbours")
    rng = np.random.RandomState(5)
    draw = lambda *s: rng.randn(*s).astype(np.float32)
    q, k, v = draw(b, 1, 4, d), draw(b, cap, 2 * d), draw(b, cap, 2 * d)
    selected = attention.select_mask(
        jnp.asarray(scores[:1]), attention.visible_slots(cursor, 1, cap)[None],
        TOPK)
    clean = attention.sparse_attention_masked(
        jnp.asarray(q), joined(jnp.asarray(k), jnp.asarray(v), d), selected,
        sm_scale=0.1)
    unselected = np.setdiff1d(np.arange(cap), want[0])
    k[:, unselected], v[:, unselected] = np.nan, np.nan
    slots, count, _ = attention.selected_slots_streamed(
        jnp.asarray(scores[:1, 0]), cursor, TOPK)
    got = attention.sparse_attention_gathered(
        jnp.asarray(q), joined(jnp.asarray(k), jnp.asarray(v), d), slots,
        count, sm_scale=0.1)
    assert rel(got, clean) < 1e-6


def _selection_case(case, cap, rng):
    """Scores (3, capacity) float32 of a token step for ``case``."""
    scores = rng.randn(3, cap).astype(np.float32)
    if case == "ties":
        # 40 equal scores straddle the 16th place in every row, among
        # a few above them: the lowest positions go first
        scores[:, 1:120:3] = 25.0
        scores[:, [7, 50, 99]] = 30.0
    elif case == "zeros":
        # the indexer's exact zeros (every relu 0): all tied, in one row
        # beside a row of random scores and one that is mostly zeros
        scores[0] = 0.0
        scores[2, 40:] = 0.0
    return scores


@pytest.mark.parametrize("case", ["random", "ties", "zeros"])
@pytest.mark.parametrize("cap", [256, 1024])
@pytest.mark.parametrize("cursor", [4, 15, 130, 255])
def test_one_kernel_lists_what_the_mask_selects(cursor, cap, case):
    """``selected_slots_streamed``: from the scores to the slot list in
    one kernel, with fewer than ``topk`` visible (cursor 4), exactly
    ``topk`` (15) and more; its slots and counts are those of
    ``select_mask`` (and the reference's selection) in rising order,
    slot 0 after them, and ``tied`` reads 1 where equal scores
    straddled the ``topk``-th place."""
    rng = np.random.RandomState(cursor + cap)
    scores = _selection_case(case, cap, rng)
    visible = attention.visible_slots(cursor, 1, cap)[0]
    slots, count, tied = attention.selected_slots_streamed(
        jnp.asarray(scores), cursor, TOPK)
    mask = np.asarray(attention.select_mask(
        jnp.asarray(scores)[:, None], visible[None, None], TOPK))[:, 0]
    straddled = False
    for i in range(3):
        want = np.nonzero(mask[i])[0]
        np.testing.assert_array_equal(
            np.asarray(ref.select(jnp.asarray(scores[i:i + 1]), visible,
                                  TOPK))[0], mask[i])
        n = int(count[i])
        assert n == len(want) == min(TOPK, cursor + 1)
        np.testing.assert_array_equal(np.asarray(slots[i, :n]), want)
        assert not np.asarray(slots[i, n:]).any()
        keys = np.sort(scores[i, :cursor + 1])[::-1]
        straddled |= bool(cursor + 1 > TOPK and keys[TOPK - 1] == keys[TOPK])
    assert int(tied[0]) == straddled
    assert straddled == (case != "random" and cursor + 1 > TOPK)


def test_the_token_step_selects_in_one_kernel(monkeypatch):
    """The gathered path's selection is ``selected_slots_streamed``
    alone: neither ``select_mask_streamed`` nor ``select_mask`` runs,
    nothing is conditional, and the step is three kernels (indexer,
    selection, attention) with no XLA operation between the scores and
    the list."""
    monkeypatch.setattr(attention, "_mosaic", lambda: True)

    def refused(*a, **k):
        raise AssertionError("the token step called the mask's search")

    monkeypatch.setattr(attention, "select_mask_streamed", refused)
    monkeypatch.setattr(attention, "select_mask", refused)
    b, cap, d = 2, 256, 128
    rng = np.random.RandomState(3)
    draw = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    args = (draw(b, 1, 4, d), draw(b, 1, 3, 8), draw(b, 1, 3),
            draw(b, cap, 2, d), draw(b, cap, 8))
    assert attention.sparse_attention_path(1, 4, 1, d, cap, jnp.float32,
                                           TOPK) == "gathered"
    run = lambda *a: attention.sparse_ring_attention(
        *a, 200, topk=TOPK, sm_scale=0.1)
    jaxpr = jax.make_jaxpr(run)(*args)
    prims = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert prims.count("pallas_call") == 3 and "cond" not in prims
    indexer, listing, attending = [e for e in jaxpr.jaxpr.eqns
                                   if e.primitive.name == "pallas_call"]
    # the list's kernel reads the indexer's scores through reshapes
    # alone (bitcasts: the indexer writes them (batch, capacity)), and
    # the attention's takes the slots and counts as the list made them
    made = {v: e for e in jaxpr.jaxpr.eqns for v in e.outvars}
    scores = listing.invars[1]
    while made[scores] is not indexer:
        assert made[scores].primitive.name == "reshape"
        scores = made[scores].invars[0]
    assert attending.invars[:2] == listing.outvars[:2]
    ctx, tied = run(*args)
    assert np.isfinite(np.asarray(ctx)).all() and int(tied) == 0


def test_equal_scores_go_to_the_lowest_positions():
    """Every third position scores 1, the rest 0, 61 visible: 21 are
    tied for 16 places, and program (both forms of the selection) and
    reference keep the first 16 of them; a tie at zero between
    +0.0 and -0.0 is a tie."""
    cap = 64
    scores = jnp.zeros((1, 1, cap)).at[0, 0, ::3].set(1.0)
    visible = attention.visible_slots(60, 1, cap)[None]
    want = np.arange(0, 48, 3)
    mask = attention.select_mask(scores, visible, TOPK)
    np.testing.assert_array_equal(np.nonzero(np.asarray(mask[0, 0]))[0], want)
    np.testing.assert_array_equal(np.nonzero(np.asarray(
        attention.select_mask_streamed(scores, 60, TOPK)[0, 0],
        np.float32))[0], want)
    np.testing.assert_array_equal(np.nonzero(np.asarray(ref.select(
        scores[0], visible[0], TOPK))[0])[0], want)
    # the indexer's own zero: weights of either sign over a relu of 0
    q, ring = jnp.zeros((1, 1, 2, 8)), acts((1, cap, 8))
    signed = attention.indexer_scores(q, jnp.asarray([[[-1.0, 1.0]]]), ring)
    assert not np.signbit(np.asarray(signed)).any()
    assert not np.signbit(np.asarray(ref.index_scores(
        q[0], jnp.asarray([[-1.0, 1.0]]), ring[0]))).any()


def test_the_path_is_chosen_from_the_shapes(monkeypatch):
    path = attention.sparse_attention_path
    bf16 = jnp.bfloat16
    # off a TPU: the mask over dense attention, whatever the shapes
    assert path(1, 32, 4, 128, 32768, bf16, 2048) == "masked"
    assert path(256, 32, 4, 128, 32768, bf16, 2048) == "masked"
    monkeypatch.setattr(attention, "_mosaic", lambda: True)
    # the cell's shapes: a token step fetches the slots it selected, a
    # chunk (whose queries together select nearly every slot) streams
    # the ring through the mask
    assert path(1, 32, 4, 128, 32768, bf16, 2048) == "gathered"
    assert path(256, 32, 4, 128, 32768, bf16, 2048) == "streamed"
    assert path(1, 32, 4, 128, 131072, bf16, 2048) == "gathered"
    assert path(2, 32, 4, 128, 131072, bf16, 2048) == "streamed"
    # a token step over a ring not much longer than topk: streamed
    ratio = attention._GATHER_RATIO
    assert path(1, 32, 4, 128, 4096, bf16, 2048) == "streamed"
    assert path(1, 32, 4, 128, 2048 * ratio, bf16, 2048) == "gathered"
    assert path(1, 32, 4, 128, 2048 * ratio - 1024, bf16, 2048) == "streamed"
    assert path(1, 32, 4, 128, 32768, bf16, 32768 // ratio + 8) == "streamed"
    assert path(1, 32, 4, 128, 32768, jnp.float32, 2048) == "gathered"
    # a list of whole turns of the kernel's loop
    assert path(1, 32, 4, 128, 32768, bf16, 2047) == "streamed"
    # a chunk of any length (padded to whole sublane tiles of 8)
    assert path(191, 32, 4, 128, 32768, bf16, 2048) == "streamed"
    # what the kernels do not take
    assert path(1, 32, 4, 64, 32768, bf16, 2048) == "masked"
    assert path(256, 32, 4, 64, 32768, bf16, 2048) == "masked"
    assert path(256, 32, 4, 128, 32768, jnp.float64, 2048) == "masked"
    assert path(256, 32, 4, 128, 1000, bf16, 2048) == "masked"
    assert path(256, 32, 4, 128, 256, bf16, 2048) == "masked"   # output()


def test_output_and_the_served_path_agree_with_the_reference(net, ids):
    want = np.asarray(ref.forward(CFG, net.params, ids))
    assert rel(net.output(ids), want) < 1e-5
    dense = ref.forward(CFG, net.params, ids, dense_attention=True)
    assert rel(dense, want) > 0.1
    wrong = ref.forward(CFG, net.params, ids, experts_held=[0, 1, 2, 3])
    assert rel(wrong, want) > 0.1
    with InferenceEngine(net, max_batch_size=4) as engine:
        assert engine.prefill_session("s", ids[:, :29], chunk=8,
                                      cache_len=64) == 29
        got = [engine.predict_session("s", ids[:, t:t + 1])
               for t in range(29, 40)]
    assert rel(np.stack(got, axis=1), want[:, 29:]) < 1e-5


def test_the_streamed_form_serves_the_same(monkeypatch, ids):
    """The predicate told that Mosaic is there: chunks of 8, a remainder
    of 5 and the token step go through the kernels (interpreted
    here)."""
    monkeypatch.setattr(attention, "_mosaic", lambda: True)
    # a head has to fill the 128 lanes for the streamed form
    wide = {**CFG, "head_dim": 128, "num_attention_heads": 2,
            "num_key_value_heads": 1, "vocab_size": 256}
    net = build(wide, cache_len=128)
    layer = net.vertices["L0_attn"].layer
    carry = net._init_carries(3, cache_len=128)["L0_attn"]
    assert (layer.attention_path(8, carry), layer.attention_path(1, carry)) \
        == ("streamed", "streamed")
    want = np.asarray(ref.forward(wide, net.params, ids))
    before = monitor.counter("sparse_attention_steps_total", "").value(
        path="streamed")
    with InferenceEngine(net, max_batch_size=4) as engine:
        # a remainder chunk of 5 positions first, then three of 8
        engine.prefill_session("s", ids[:, :29], chunk=8, cache_len=128)
        out = engine.generate("s", ids[:, 29:30], 3)
    assert monitor.counter("sparse_attention_steps_total", "").value(
        path="streamed") - before == 4 + 3
    sequence = np.concatenate([ids[:, :30], out.ids[:, :-1]], axis=1)
    want = np.asarray(ref.forward(wide, net.params, sequence, last=3))
    kept = np.stack([np.asarray(k) for k in out.kept_logits], axis=1)
    assert rel(kept, want[[0, 2]]) < 1e-5


def test_a_token_step_over_a_long_ring_is_served_gathered(monkeypatch, ids):
    """A ring of 256 slots, 16 selected: the prefill's chunks stream,
    the token steps fetch by descriptor, and
    ``sparse_attention_steps_total{path}`` says which."""
    monkeypatch.setattr(attention, "_mosaic", lambda: True)
    wide = {**CFG, "head_dim": 128, "num_attention_heads": 2,
            "num_key_value_heads": 1, "vocab_size": 256}
    net = build(wide, cache_len=256)
    layer = net.vertices["L0_attn"].layer
    carry = net._init_carries(3, cache_len=256)["L0_attn"]
    assert [a.shape for a in carry] == [(3, 256, 2, 128), (3, 256, 8), ()]
    assert (layer.attention_path(8, carry), layer.attention_path(1, carry)) \
        == ("streamed", "gathered")
    steps = monitor.counter("sparse_attention_steps_total", "")
    before = {p: steps.value(path=p) for p in ("gathered", "streamed")}
    with InferenceEngine(net, max_batch_size=4) as engine:
        engine.prefill_session("s", ids[:, :29], chunk=8, cache_len=256)
        out = engine.generate("s", ids[:, 29:30], 3)
    assert steps.value(path="streamed") - before["streamed"] == 4
    assert steps.value(path="gathered") - before["gathered"] == 3
    # the steps whose selection settled ties (the indexer's exact zeros
    # can straddle the 16th place of 30-32 rows), a layer, beside the
    # experts' counts and in the counter
    assert set(out.selection_tied) == {"L0_attn", "L1_attn"}
    assert set(out.selection_tied.values()) <= {0, 1, 2, 3}
    assert set(out.experts_spilled) == {"L0_moe", "L1_moe"}
    tied = monitor.counter("sparse_select_tied_total", "")
    for layer, steps in out.selection_tied.items():
        assert tied.value(model=engine._name, layer=layer) >= steps
    sequence = np.concatenate([ids[:, :30], out.ids[:, :-1]], axis=1)
    want = np.asarray(ref.forward(wide, net.params, sequence, last=3))
    kept = np.stack([np.asarray(k) for k in out.kept_logits], axis=1)
    assert rel(kept, want[[0, 2]]) < 1e-5


# ------------------------------------------------------------- the experts
def test_softmax_routing_agrees_with_the_reference(net):
    layer, p = net.vertices["L1_moe"].layer, net.params["L1_moe"]
    assert (layer.scoring, layer.n_experts, layer.top_k, layer.n_shared,
            layer.held()) == ("softmax", 8, 2, 0, HELD)
    x = acts((18, 64))
    idx, w = layer.route(p, x)
    want = np.asarray(ref.routing(CFG, p, x))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(idx), np.asarray(w), axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(got.sum(1), 1.0, rtol=1e-5)
    y = layer.forward(p, layer.init_state(), x[None], train=False)[0]
    assert rel(y[0], ref.moe(CFG, p, x, HELD)) < 1e-5


def test_sigmoid_routing_is_what_it_was():
    """The default scoring, bit for bit the formula it had before
    ``scoring`` existed."""
    layer = decoder.MixtureOfExperts(
        n_in=64, n_out=64, n_experts=8, top_k=2, width=32,
        routed_scaling=2.5, router_bias_std=0.2, weight_init="distribution",
        dist=Distribution(kind="normal", std=0.3))
    assert layer.scoring == "sigmoid"
    p, x = layer.init_params(jax.random.PRNGKey(0)), acts((18, 64))
    g = jax.nn.sigmoid(jnp.matmul(x, p["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(g + p["router_bias"], 2)
    w = jnp.take_along_axis(g, idx, axis=1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * 2.5
    got_idx, got_w = layer.route(p, x)
    np.testing.assert_array_equal(got_idx, idx)
    np.testing.assert_array_equal(got_w, w)


def test_the_shares_of_all_holders_add_up_to_the_uncut_layer():
    """Four chips hold ids 0-1, 2-3, 4-5, 6-7 of a router of 8: the
    shares' results, summed, are the uncut reference's expert layer
    (there is no shared expert to count once)."""
    x = acts((2, 9, 64))
    cut = lambda held: {**CFG, "num_experts": len(held),
                        "builder_args": {"experts_held": held}}
    whole_cfg = {**CFG, "num_experts": 8, "published": {}}
    whole = build(whole_cfg, experts_held=None)
    want = ref.moe(whole_cfg, whole.params["L1_moe"], x)
    total = 0
    for held in ([0, 1], [2, 3], [4, 5], [6, 7]):
        share = build(cut(held), experts_held=held)
        layer, p = share.vertices["L1_moe"].layer, share.params["L1_moe"]
        # a share holds what the whole layer has: the same draws
        f = 32
        for j, e in enumerate(held):
            np.testing.assert_array_equal(
                p["Wg"][:, j * f:(j + 1) * f],
                whole.params["L1_moe"]["Wg"][:, e * f:(e + 1) * f])
        y = layer.forward(p, layer.init_state(), x, train=False)[0]
        assert rel(y, ref.moe(cut(held), p, x, held)) < 1e-5
        total = total + y
    assert rel(total, want) < 1e-5


# ------------------------------------------------------------- the builder
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
])
def test_the_builder_still_makes_the_two_latent_nets(name, digest,
                                                     parameters, vertices):
    """From the benchmark's own files: the vertex names and the shapes
    of every parameter, as the builder made them before it read
    ``sa_config`` (digests taken from the parent commit's builder)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{name}.json")) as fh:
        cfg = json.load(fh)
    got = _shape_digest(cfg, cache_len=64, **cfg["builder_args"])
    assert got == (digest, parameters, vertices)


def test_the_builder_reads_the_sparse_files_keys(net):
    assert list(net.vertices) == ["embed"] + [
        f"L{i}_{part}" for i in range(2) for part in (
            "attn_norm", "attn", "attn_add", "ffn_norm", "moe", "ffn_add")
    ] + ["final_norm", "head"]
    layer = net.vertices["L1_attn"].layer
    assert isinstance(layer, decoder.SparseGroupedQueryAttention)
    assert (layer.n_heads, layer.n_kv_heads, layer.head_dim,
            layer.index_heads, layer.index_dim, layer.topk,
            layer.rope_theta) == (4, 2, 16, 3, 8, TOPK, 1e7)
    assert sorted(net.params["L1_attn"]) == sorted(layer.param_order())
    assert sorted(net.params["L1_moe"]) == ["Wd", "Wg", "Wu", "router",
                                           "router_bias"]
    # dense layers where the file says so
    mixed = from_config({**CFG, "mlp_only_layers": [0]}, **ARGS)
    assert "L0_ffn" in mixed.vertices and "L1_moe" in mixed.vertices
    with pytest.raises(ValueError, match="4 of 8 routed experts"):
        from_config(CFG, cache_len=64)
    with pytest.raises(ValueError, match="no attention"):
        from_config({k: v for k, v in CFG.items() if k != "sa_config"},
                    **ARGS)
    # the conf round-trips with the new layer and the new field
    conf = from_config(CFG, **ARGS)
    again = type(conf).from_json(conf.to_json())
    assert again.to_json() == conf.to_json()


def test_the_real_file_is_the_catalog_rows_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye_vl2_30b_a3b.json")) as fh:
        cfg = json.load(fh)
    g = from_config(cfg, cache_len=32768, **cfg["builder_args"])
    layer = g.vertices["L5_attn"].layer
    assert (layer.n_in, layer.n_heads, layer.n_kv_heads, layer.head_dim,
            layer.index_heads, layer.index_dim, layer.topk) == (
                2048, 32, 4, 128, 16, 64, 2048)
    moe = g.vertices["L5_moe"].layer
    assert (moe.n_experts, moe.top_k, moe.width, moe.n_shared, moe.scoring,
            moe.held(), moe.routed_scaling) == (
                128, 8, 768, 0, "softmax", list(range(16)), 1.0)
    digest, parameters, vertices = _shape_digest(
        cfg, cache_len=64, **cfg["builder_args"])
    # ISSUE 37's arithmetic: 6 x 96.9 M + 2 x 38.9 M
    assert parameters == 659190784 and vertices == 1 + 6 * 6 + 2


# ------------------------------------------------------------ the sessions
def test_fork_grow_and_chunked_prefill_of_the_three_rings(net, ids):
    layer = net.vertices["L0_attn"].layer
    carry = layer.init_carry(3, jnp.float32, 32)
    # keys and values share a ring: a slot's 2 + 2 heads of 16
    assert [a.shape for a in carry] == [(3, 32, 4, 16), (3, 32, 8), ()]
    grown = layer.grow_carry(carry, 64)
    assert [a.shape for a in grown] == [(3, 64, 4, 16), (3, 64, 8), ()]
    with pytest.raises(ValueError, match="shrink"):
        layer.grow_carry(grown, 32)
    want = np.asarray(ref.forward(CFG, net.params, ids))
    with InferenceEngine(net, max_batch_size=4) as engine:
        cache = engine.sessions
        engine.prefill_session("snap", ids[:, :-1], chunk=5, cache_len=64)
        held = jax.tree.map(np.asarray, cache.get_carries("snap"))
        engine.fork_session("snap", "a")
        a = engine.generate("a", ids[:, -1:], 5)
        engine.fork_session("snap", "b")
        b = engine.generate("b", ids[:, -1:], 5)
        np.testing.assert_array_equal(a.ids, b.ids)
        for kept, now in zip(jax.tree.leaves(held), jax.tree.leaves(
                jax.tree.map(np.asarray, cache.get_carries("snap")))):
            np.testing.assert_array_equal(kept, now)
        assert cache.session_position("a") == 39 + 5
        # three sessions of 2 layers x (2 x 32 + 8) numbers a slot a row,
        # and a cursor a layer
        rings = 3 * 2 * 3 * 64 * (2 * 32 + 8) * 4
        assert monitor.gauge("serving_session_state_bytes", "").value(
            model="default", kind="sparse_kv") == rings + 3 * 2 * 4
        assert cache.state_bytes() == rings + 3 * 2 * 4
        assert monitor.gauge("sparse_attention_selected", "").value(
            model="default", layer="L1_attn") == TOPK
        # a session that outgrows its bucket hops to the next ring
        short = [engine.predict_session("s", ids[:, t:t + 1])
                 for t in range(20)]
        assert cache.session_capacity("s") == 32
    assert rel(np.stack(short, axis=1), want[:, :20]) < 1e-5
    kept = np.stack([np.asarray(k) for k in a.kept_logits], axis=1)
    sequence = np.concatenate([ids, a.ids[:, :-1]], axis=1)
    assert rel(kept, np.asarray(ref.forward(
        CFG, net.params, sequence, last=5))[[0, 2]]) < 1e-5


def test_both_kinds_of_ring_state_live_in_one_session_cache():
    """A graph with a latent-attention vertex and a sparse one: the
    session cache tells their state apart by the layers' ``STATE_KIND``
    (no branch on the model), gauges each kind's bytes, and forks, grows
    and steps both without one touching the other."""
    b = (NeuralNetConfiguration.builder().seed(5).updater("sgd")
         .weight_init("distribution")
         .dist(Distribution(kind="normal", std=0.3))
         .activation("identity").dtype("float32"))
    g = b.graph_builder()
    g.add_inputs("ids")
    g.add_layer("embed", decoder.TokenEmbedding(n_in=64, n_out=32), "ids")
    g.add_layer("latent", decoder.LatentAttention(
        n_in=32, n_out=32, n_heads=2, q_rank=16, kv_rank=8, d_nope=8,
        d_rope=4, d_v=8, cache_len=32), "embed")
    g.add_layer("sparse", decoder.SparseGroupedQueryAttention(
        n_in=32, n_out=32, n_heads=2, n_kv_heads=1, head_dim=8,
        index_heads=2, index_dim=4, topk=4, cache_len=32), "latent")
    g.add_layer("head", decoder.LMHead(n_in=32, n_out=64), "sparse")
    g.set_outputs("head")
    net = ComputationGraph(g.build()).init()
    ids = np.random.RandomState(1).randint(0, 64, (2, 12)).astype(np.int32)
    full = np.asarray(net.output(ids))
    cache = SessionCache(net, name="both", ttl_s=0.0)
    cache.prefill("p", ids[:, :7], chunk=3, cache_len=16)
    cache.fork("p", "q")
    steps = [cache.step("q", ids[:, t:t + 1, None]) for t in range(7, 12)]
    assert rel(np.concatenate(steps, axis=1), full[:, 7:]) < 1e-5
    carries = cache.get_carries("p")
    latent = sum(a.nbytes for a in carries["latent"])
    sparse = sum(a.nbytes for a in carries["sparse"])
    assert latent == 2 * 16 * (8 + 4) * 4 + 4
    assert sparse == 2 * 16 * (8 + 8 + 4) * 4 + 4
    value = lambda kind: monitor.gauge("serving_session_state_bytes",
                                       "").value(model="both", kind=kind)
    assert (value("latent"), value("sparse_kv")) == (2 * latent, 2 * sparse)
    assert cache.state_bytes() == 2 * (latent + sparse)
    # the fork grew past 16 slots... no: 12 positions fit; grow by hand
    grown = net.grow_decode_carries(cache.get_carries("q"), 32)
    assert grown["latent"][0].shape[1] == grown["sparse"][0].shape[1] == 32
    assert int(cache.get_carries("p")["sparse"][-1]) == 7  # untouched


# --------------------------------------------------------------- the scopes
def test_the_parts_a_trace_has_to_tell_apart_have_scopes_of_their_own(net):
    text = net._token_step_fn.lower(
        net.params, net.net_state, net._init_carries(2, cache_len=64),
        jnp.zeros((2, 1), jnp.int32), net.zero_expert_counts()).as_text(
            debug_info=True)
    for scope in ("layer.L1_attn.indexer", "layer.L1_attn.select",
                  "layer.L1_attn.sparse_attention", "layer.L1_moe.router",
                  "layer.L1_moe.experts", "layer.L0_attn_add"):
        assert f"/{scope}/" in text, scope
    assert "layer.L1_moe.shared" not in text
    names = [line.split('"')[1] for line in text.splitlines()
             if line.startswith("#loc") and "/layer.L1_attn.select/" in line]
    # the selection as a mask: the radix search's counts, the tie's cond
    assert any(n.endswith("/reduce_sum") for n in names)
    assert any(n.endswith("/cond") for n in names)
    assert monitor.parse_op_name(
        "jit(run)/layer.L1_attn/layer.L1_attn.select/cond") == (
            "layer.L1_attn.select", "forward")


# ------------------------------------------------- weights laid once (PR 38)
def test_a_net_that_lays_nothing_is_served_its_parameters_as_they_are(ids):
    """No layer of this decoder lays its weights: prepared for serving
    or not, the net holds no laid tree, ``token_step`` is handed
    ``params`` itself, and the step lowers to the same program text."""
    def lowered(model):
        return model._token_step_fn.lower(
            model.served_params(), model.net_state,
            model._init_carries(3, cache_len=64),
            jnp.zeros((3, 1), jnp.int32),
            model.zero_expert_counts()).as_text()

    gauge = monitor.gauge("serving_laid_weight_bytes", "")
    gauge.set(0, vertex="L0_attn")     # whatever an earlier file laid
    plain = build()
    served = ComputationGraph(from_config(CFG, **ARGS)).init(
        for_inference=True)
    for model in (plain, served):
        assert model.laid_vertices() == [] and model._lay_programs == {}
        assert model.served_params() is model.params and model._laid == {}
    assert lowered(served) == lowered(plain)
    out = served.token_step(served.prefill_step(
        served._init_carries(3, cache_len=64), ids[:, :8]), ids[:, 8:9])
    want = plain.token_step(plain.prefill_step(
        plain._init_carries(3, cache_len=64), ids[:, :8]), ids[:, 8:9])
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert gauge.value(vertex="L0_attn") == 0
