"""The routed experts' grouped form (``ops.experts.grouped_experts``)
against the dense form of ``MixtureOfExperts.forward`` on the CPU, in
Pallas interpret mode at tiny widths; the predicate that picks the
form; the grouped form forced through the served path (prefill in
chunks, then token steps, which stay dense); and the counter that says
how often it engages.  ``tests/test_latent_ring_kernel.py`` holds the
kernel compiled by Mosaic for a described v5e at the decode cell's
widths."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mla_moe_decoder as ref
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.models.mla_moe_decoder import from_config
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import decoder
from deeplearning4j_tpu.ops import experts
from deeplearning4j_tpu.serving import InferenceEngine

HIDDEN, WIDTH, EXPERTS = 64, 32, 8
TILE = 16                       # rows a tile here: groups straddle tiles
CFG = dict(
    vocab_size=256, hidden_size=HIDDEN, num_hidden_layers=2,
    first_k_dense_replace=1, intermediate_size=160,
    moe_intermediate_size=WIDTH, n_routed_experts=EXPERTS,
    num_experts_per_tok=2, n_shared_experts=1, routed_scaling_factor=2.0,
    norm_topk_prob=True, num_attention_heads=4, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64,
                  "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"},
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def force(monkeypatch, form, tm=TILE):
    """``form`` (a name, or a function of the token count) for every
    expert layer, the kernel at ``tm`` rows a tile; it asks the real
    backend whether to interpret, and does."""
    pick = form if callable(form) else (lambda tokens: form)
    monkeypatch.setattr(decoder, "moe_experts_path",
                        lambda tokens, *a, **k: pick(tokens))
    monkeypatch.setattr(decoder, "grouped_experts", functools.partial(
        experts.grouped_experts, tm=tm))


#: tokens, picks a token, experts held, the selection bias that shapes
#: the groups (expert -> bias; a large one wins every token, a very
#: negative one none) and, where it is not ``EXPERTS``, the router's
#: width: a share of a wider router, most of whose picks name experts
#: that lie elsewhere
WIDE = 24
CASES = {
    "uneven_groups": (37, 2, None, {1: 0.5, 6: -0.5}),
    "an_expert_with_no_row": (37, 2, None, {3: -100.0}),
    "one_expert_with_every_row": (24, 1, None, {5: 100.0}),
    "tokens_no_multiple_of_the_tile": (21, 2, None, {}),
    "one_pick": (40, 1, None, {}),
    "four_picks": (40, 4, None, {}),
    "an_unordered_share": (37, 2, [5, 2], {}),
    "a_share_no_token_chose": (16, 2, [5, 2], {5: -100.0, 2: -100.0}),
    "a_quarter_of_a_wide_router": (40, 3, list(range(6, 12)), {}, WIDE),
    "a_wide_share_with_one_busy_expert": (24, 3, list(range(6, 12)),
                                          {7: 100.0}, WIDE),
    "a_wide_share_under_one_tile": (5, 3, list(range(6)), {}, WIDE),
    "a_wide_share_no_token_chose": (16, 3, [0, 1, 2, 3],
                                    {e: -100.0 for e in range(4)}, WIDE),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype,bound", [("float32", 1e-5),
                                         ("bfloat16", 8e-3)])
def test_grouped_agrees_with_dense(monkeypatch, dtype, bound, case):
    """float32: the two forms differ by the order of their sums.
    bfloat16: the dense form rounds the two products before ``silu``
    and the weighted activation once more, the grouped form keeps them
    in float32 up to the one rounding before the last product."""
    tokens, top_k, held, bias, n_experts = (*CASES[case], EXPERTS)[:5]
    layer = decoder.MixtureOfExperts(
        n_in=HIDDEN, n_out=HIDDEN, n_experts=n_experts, top_k=top_k,
        width=WIDTH, n_shared=1, routed_scaling=2.0, experts_held=held,
        weight_init="distribution",
        dist=decoder.Distribution(kind="normal", std=0.3))
    params = layer.init_params(jax.random.PRNGKey(7), jnp.dtype(dtype))
    params["router_bias"] = jnp.asarray(
        [bias.get(e, 0.0) for e in range(n_experts)], jnp.float32)
    x = jnp.asarray(np.random.RandomState(1).randn(1, tokens, HIDDEN),
                    jnp.dtype(dtype))
    want, counted = layer.forward(params, layer.init_state(), x, train=False)
    force(monkeypatch, "grouped")
    got, state = layer.forward(params, layer.init_state(), x, train=False)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(state["expert_tokens"],
                                  counted["expert_tokens"])
    assert state["expert_tokens"].shape == (n_experts,)
    assert int(state["expert_tokens"].sum()) == tokens * top_k
    if case.endswith("no_token_chose"):         # the shared expert alone
        want = decoder._gated(x, params["Sg"], params["Su"], params["Sd"])
    elif held is not None:
        # a pick of an absent expert weighs nothing: most do
        on_held = int(np.asarray(state["expert_tokens"])[held].sum())
        assert 0 < on_held < tokens * top_k
    assert rel(got, want) < bound
    if held is not None and dtype == "float32":
        cfg = {**CFG, "num_experts_per_tok": top_k}
        assert rel(got, ref.moe(cfg, params, x, experts_held=held)) < 1e-5


def test_group_tiles_visit_every_meeting_of_a_tile_and_a_group():
    """Groups of 0, 5, 16, 0, 30 and 13 rows in 64 rows, tiles of 16:
    each (tile, group) pair that shares a row is visited once, in row
    order, and the spare visits repeat the last one."""
    sizes = jnp.asarray([0, 5, 16, 0, 30, 13], jnp.int32)
    group, tile, live, offsets = experts.group_tiles(sizes, 64, 16)
    assert offsets.tolist() == [0, 0, 5, 21, 21, 51, 64]
    want = [(1, 0), (2, 0), (2, 1), (4, 1), (4, 2), (4, 3), (5, 3)]
    assert int(live[0]) == len(want)
    assert group.shape == tile.shape == (64 // 16 + 6 - 1,)
    visits = list(zip(group.tolist(), tile.tolist()))
    assert visits[:len(want)] == want
    assert set(visits[len(want):]) == {want[-1]}
    # no row at all: nothing is live, and the indices stay in range
    group, tile, live, _ = experts.group_tiles(jnp.zeros((3,), jnp.int32),
                                               32, 16)
    assert int(live[0]) == 0 and set(tile.tolist()) == {0}


# ------------------------------------------------------------ the predicate
def test_the_path_is_chosen_from_the_arguments(monkeypatch):
    path = experts.moe_experts_path
    cell = (64, 4, 3584, 1024)          # held, picks, hidden, width
    # the CPU default: dense, whatever the arguments
    assert path(2048, *cell, jnp.bfloat16, False) == "dense"
    monkeypatch.setattr(experts, "_mosaic", lambda: True)
    assert path(2048, *cell, jnp.bfloat16, False) == "grouped"
    assert path(1984, *cell, jnp.bfloat16, False) == "grouped"
    assert path(2048, *cell, jnp.float32, False) == "grouped"
    assert path(64, *cell, jnp.bfloat16, False) == "dense"  # the token step
    assert path(2048, *cell, jnp.bfloat16, True) == "dense"     # training
    assert path(2048, *cell, jnp.float64, False) == "dense"
    assert path(2048, 4, 4, 3584, 1024, jnp.bfloat16, False) == "dense"
    # a four-chip share of 16 experts still leaves most unchosen
    assert path(2048, 16, 4, 3584, 1024, jnp.bfloat16, False) == "grouped"
    # widths that are no whole lanes; a block beyond the kernel's VMEM
    assert path(2048, 8, 2, 64, 32, jnp.float32, False) == "dense"
    assert path(2048, 64, 4, 65536, 1024, jnp.bfloat16, False) == "dense"
    # the crossing stays above the token step's 64 rows with margin
    assert experts._GROUPED_MIN_TOKENS >= 4 * 64
    # a block is taken whole where it fits, halved where it does not
    assert experts.grouped_tile_columns(3584, 1024, 2) == 1024
    assert experts.grouped_tile_columns(1024, 3584, 2) == 3584
    assert experts.grouped_tile_columns(7168, 2048, 2) == 1024
    assert experts.grouped_tile_columns(2048, 7168, 2) == 3584
    # one chip of sixteen: 12 held of a 192-wide router, 8 picks.  The
    # predicate counts neither the router's width nor how few picks
    # land here: the token step's 256 rows stay dense, a chunk of 2,048
    # is grouped
    share = (12, 8, 7168, 2048)
    assert path(256, *share, jnp.bfloat16, False) == "dense"
    assert path(2048, *share, jnp.bfloat16, False) == "grouped"
    assert path(2048, 8, 8, 7168, 2048, jnp.bfloat16, False) == "dense"


# ------------------------------------------------- through the served path
def _steps(path):
    return monitor.counter("moe_experts_steps_total", "").value(path=path)


def test_grouped_prefill_in_chunks_then_decode_agrees_with_the_full_forward(
        monkeypatch):
    """Chunks of 4 tokens a row (12 tokens, 24 pairs: two tiles of 16)
    take the grouped form, the token steps (3 tokens) the dense one; the
    rings the steps read are what the grouped chunks wrote."""
    kernel, calls = experts.grouped_experts, []

    def spy(x, *args, **kw):
        calls.append(x.shape[0])                # tokens
        return kernel(x, *args, **kw)

    force(monkeypatch, lambda tokens: "grouped" if tokens >= 12 else "dense")
    monkeypatch.setattr(decoder, "grouped_experts",
                        functools.partial(spy, tm=TILE))
    net = ComputationGraph(from_config(
        CFG, dtype="float32", cache_len=32, init_std=0.1, hc_alpha_init=0.5,
        hc_bias_std=1.0, router_bias_std=0.2, seed=3)).init()
    ids = np.random.RandomState(0).randint(0, 256, (3, 20)).astype(np.int32)
    want = np.asarray(ref.forward(CFG, net.params, ids))
    grouped, dense = _steps("grouped"), _steps("dense")
    with InferenceEngine(net, max_batch_size=4) as engine:
        assert engine.prefill_session("snap", ids[:, :13], chunk=4,
                                      cache_len=32) == 13
        # one expert layer, two signatures: the remainder chunk of one
        # token a row stays dense, the three chunks of four are grouped
        assert calls == [12]
        assert (_steps("grouped"), _steps("dense")) == (grouped + 3,
                                                        dense + 1)
        engine.fork_session("snap", "s")
        got = [engine.predict_session("s", ids[:, t:t + 1])
               for t in range(13, 20)]
        assert rel(np.stack(got, axis=1), want[:, 13:]) < 1e-5
        # counted on the host, once a launched step, by the same predicate
        engine.fork_session("snap", "g")
        out = engine.generate("g", ids[:, 13:14], 3)
        assert (_steps("grouped"), _steps("dense")) == (grouped + 3,
                                                        dense + 4)
        # a first step that is fed a long chunk takes what the predicate
        # gives for its token count
        engine.generate("long", ids[:, :4], 2)
        assert (_steps("grouped"), _steps("dense")) == (grouped + 4,
                                                        dense + 5)
    assert rel(np.asarray(out.kept_logits[0]), want[[0, 2], 13]) < 1e-5
    assert calls == [12, 12]    # generate's long first step, traced anew
    # output() on the full sequences: 60 tokens, grouped too here
    assert rel(net.output(ids), want) < 1e-5
    assert calls[-1] == 60


def test_the_dense_default_counts_dense_steps():
    net = ComputationGraph(from_config(
        CFG, dtype="float32", cache_len=32, seed=3)).init()
    ids = np.random.RandomState(1).randint(0, 256, (2, 6)).astype(np.int32)
    grouped, dense = _steps("grouped"), _steps("dense")
    with InferenceEngine(net, max_batch_size=4) as engine:
        engine.prefill_session("s", ids[:, :-1], chunk=2, cache_len=32)
        engine.generate("s", ids[:, -1:], 4)
    # chunks of 1, 2 and 2 tokens a row, then four token steps
    assert (_steps("grouped"), _steps("dense")) == (grouped, dense + 7)
