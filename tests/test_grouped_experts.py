"""The routed experts' grouped form (``ops.experts.grouped_experts``)
and, under a share, the dense form over the tokens that picked a held
expert (``held_rows_experts``) against the dense form of
``MixtureOfExperts.forward`` on the CPU, the kernel in Pallas interpret
mode at tiny widths; the predicate that picks the form; each form forced
through the served path (prefill in chunks, then token steps); and the
counters that say how often a form engages and how often it spills.
``tests/test_latent_ring_kernel.py`` holds the kernel compiled by Mosaic
for a described v5e at the decode cells' widths."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mla_moe_decoder as ref
from benchmark.reference import mla_moe_plain as plain
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
#: that lie elsewhere and have no row; last, where it is not 0, whether
#: the held pairs outgrow the rows (``experts.grouped_rows``: twice an
#: even router's, in tiles of 16) and take further rounds
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
    # 120 pairs, 30 of them held by an even router: 64 rows
    "a_quarter_of_a_wide_router": (40, 3, list(range(6, 12)), {}, WIDE),
    "a_wide_share_with_one_busy_expert": (24, 3, list(range(6, 12)),
                                          {7: 100.0}, WIDE),
    "a_wide_share_under_one_tile": (5, 3, list(range(6)), {}, WIDE),
    "a_wide_share_no_token_chose": (16, 3, [0, 1, 2, 3],
                                    {e: -100.0 for e in range(4)}, WIDE),
    # 32 rows, one tile; expert 0 takes a pick of every token and no
    # other held expert any: 32 held pairs, the last row the last pair
    "held_pairs_fill_the_rows": (32, 3, [0, 1, 2, 3],
                                 {0: 100.0, 1: -100.0, 2: -100.0,
                                  3: -100.0}, WIDE),
    # 32 rows for 40 pairs on one held expert: its group spans two rounds
    "one_held_expert_given_every_token": (40, 3, [9, 4], {4: 100.0,
                                                          9: -100.0},
                                          WIDE, 1),
    # every pick names a held expert: 120 pairs in rounds of 32 rows
    "a_share_given_every_pick": (40, 3, [2, 0, 1],
                                 {0: 100.0, 1: 100.0, 2: 100.0}, WIDE, 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype,bound", [("float32", 1e-5),
                                         ("bfloat16", 8e-3)])
def test_grouped_agrees_with_dense(monkeypatch, dtype, bound, case):
    """float32: the two forms differ by the order of their sums.
    bfloat16: the dense form rounds the two products before ``silu``
    and the weighted activation once more, the grouped form keeps them
    in float32 up to the one rounding before the last product."""
    agrees_with_dense(monkeypatch, "grouped", dtype, bound, case)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_dense_form_over_the_held_rows_agrees_with_dense(
        monkeypatch, dtype, case):
    """The same three products on the same rows, fewer of them: a
    token's result is the dense form's to the last bit in either
    precision, and 0 for a token that picked no held expert; tokens
    beyond the rows (four deviations over an even router's count, in
    tiles of 16) take a second round."""
    agrees_with_dense(monkeypatch, "held_rows", dtype, 1e-6, case)


def agrees_with_dense(monkeypatch, form, dtype, bound, case):
    tokens, top_k, held, bias, *rest = CASES[case]
    n_experts, spilled = (*rest, *(EXPERTS, 0)[len(rest):])
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
    assert int(counted["experts_spilled"]) == 0     # the dense form
    force(monkeypatch, form)
    got, state = layer.forward(params, layer.init_state(), x, train=False)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(state["expert_tokens"],
                                  counted["expert_tokens"])
    assert state["expert_tokens"].shape == (n_experts,)
    assert int(state["expert_tokens"].sum()) == tokens * top_k
    on_held = int(np.asarray(state["expert_tokens"])[layer.held()].sum())
    shape = tokens, top_k, len(layer.held()), n_experts
    if form == "grouped":
        rows, _ = experts.grouped_rows(*shape, TILE)
        assert int(state["experts_spilled"]) == spilled == int(
            on_held > rows)
    else:
        rows = experts.held_token_rows(*shape)
        on_held = int(np.isin(np.asarray(layer.route(params, x[0])[0]),
                              layer.held()).any(axis=1).sum())
        assert int(state["experts_spilled"]) == spilled == int(
            on_held > rows)
    if case == "held_pairs_fill_the_rows":
        assert on_held == rows
    if case.endswith("no_token_chose"):         # the shared expert alone
        want = decoder._gated(x, params["Sg"], params["Su"], params["Sd"])
    elif held is not None and not spilled and form == "grouped":
        # a pick of an absent expert weighs nothing: most do
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


#: a share's token step at toy widths: 24 tokens through 8 held experts
#: (in no order) of a 64-wide router, the picks a token and the
#: selection bias (expert -> bias) that shapes the groups; last, whether
#: the held pairs outgrow the one tile of 16 rows and take further rounds
STEP_HELD = [5, 2, 7, 0, 9, 11, 3, 60]
STEP_CASES = {
    "even_routing": (2, {}, 0),
    "no_held_pick": (2, {e: -100.0 for e in STEP_HELD}, 0),
    # 24 pairs on one expert: its group spans two rounds of 16 rows
    "every_pick_on_one_held_expert": (1, {7: 100.0}, 1),
}


@pytest.mark.parametrize("case", sorted(STEP_CASES))
def test_a_token_step_of_a_share_reads_only_the_picked_experts(monkeypatch,
                                                               case):
    """The grouped form as the token step of a share takes it: the rows
    and the tile from the shapes alone, 16 rows in one sublane tile, the
    kernel interpreted, against ``dense_experts`` over every held expert
    with the unchosen weighted 0.  What it says it read is the held
    experts that received a pick, once for every round their rows meet;
    with no held pick nothing is live and the routed part is 0."""
    top_k, bias, spilled = STEP_CASES[case]
    tokens, n_experts = 24, 64
    assert experts.grouped_rows(tokens, top_k, len(STEP_HELD),
                                n_experts) == (16, 16)
    layer = decoder.MixtureOfExperts(
        n_in=HIDDEN, n_out=HIDDEN, n_experts=n_experts, top_k=top_k,
        width=WIDTH, n_shared=0, experts_held=STEP_HELD,
        weight_init="distribution",
        dist=decoder.Distribution(kind="normal", std=0.3))
    params = layer.init_params(jax.random.PRNGKey(5), jnp.float32)
    params["router_bias"] = jnp.asarray(
        [bias.get(e, 0.0) for e in range(n_experts)], jnp.float32)
    x = jnp.asarray(np.random.RandomState(3).randn(tokens, HIDDEN),
                    jnp.float32)
    idx, w = layer.route(params, x)
    matrices = params["Wg"], params["Wu"], params["Wd"]
    kernel, live = experts.grouped_matmul, []

    def spy(lhs, rhs, tiles, **kw):
        live.append(tiles[2])
        return kernel(lhs, rhs, tiles, **kw)

    monkeypatch.setattr(experts, "grouped_matmul", spy)
    got, spills, read = experts.grouped_experts(
        x, idx, w, *matrices, held=STEP_HELD, n_experts=n_experts)
    hit = np.asarray(idx)[:, :, None] == np.asarray(STEP_HELD)
    combine = jnp.asarray(np.sum(np.where(hit, np.asarray(w)[:, :, None],
                                          0.0), axis=1), jnp.float32)
    want = experts.dense_experts(x, combine, *matrices)
    picked = int(hit.any(axis=(0, 1)).sum())
    assert int(spills) == spilled
    assert int(read) == picked + spilled
    if case == "no_held_pick":
        assert picked == 0 and not np.asarray(want).any()
        # the first round's three products (the loop of further rounds is
        # traced after them): nothing live, nothing written
        assert [int(v[0]) for v in live[:3]] == [0, 0, 0]
        np.testing.assert_array_equal(np.asarray(got), 0.0)
    else:
        assert 0 < picked < len(STEP_HELD)
        assert rel(got, want) < 1e-5


# ------------------------------------------------------------ the predicate
def test_the_path_is_chosen_from_the_arguments(monkeypatch):
    path = experts.moe_experts_path
    # held, the router's width, picks, hidden, width: Xing4.0 holds all
    cell = (64, 64, 4, 3584, 1024)
    # the CPU default: dense, whatever the arguments
    assert path(2048, *cell, jnp.bfloat16, False) == "dense"
    monkeypatch.setattr(experts, "_mosaic", lambda: True)
    assert path(2048, *cell, jnp.bfloat16, False) == "grouped"
    assert path(1984, *cell, jnp.bfloat16, False) == "grouped"
    assert path(2048, *cell, jnp.float32, False) == "grouped"
    assert path(64, *cell, jnp.bfloat16, False) == "dense"  # the token step
    assert path(256, *cell, jnp.bfloat16, False) == "dense"
    assert path(2048, *cell, jnp.bfloat16, True) == "dense"     # training
    assert path(2048, *cell, jnp.float64, False) == "dense"
    assert path(2048, 4, 4, 4, 3584, 1024, jnp.bfloat16, False) == "dense"
    # a four-chip share of 16 experts still leaves most unchosen; of its
    # 256 tokens 208 rows hold those that picked one, of 512 too many
    quarter = (16, 64, 4, 3584, 1024)
    assert path(2048, *quarter, jnp.bfloat16, False) == "grouped"
    assert path(512, *quarter, jnp.bfloat16, False) == "grouped"
    assert path(256, *quarter, jnp.bfloat16, False) == "held_rows"
    assert path(128, *quarter, jnp.bfloat16, False) == "dense"
    # widths that are no whole lanes; a block beyond the kernel's VMEM
    assert path(2048, 8, 8, 2, 64, 32, jnp.float32, False) == "dense"
    assert path(2048, 64, 64, 4, 65536, 1024, jnp.bfloat16,
                False) == "dense"
    # the crossings stay above Xing4.0's token step of 64 rows with margin
    assert experts._GROUPED_MIN_TOKENS >= 4 * 64
    assert experts._DENSE_TURN_ROWS >= 4 * 64
    # a block is taken whole where it fits, halved where it does not
    assert experts.grouped_tile_columns(3584, 1024, 2) == 1024
    assert experts.grouped_tile_columns(1024, 3584, 2) == 3584
    assert experts.grouped_tile_columns(7168, 2048, 2) == 1024
    assert experts.grouped_tile_columns(2048, 7168, 2) == 3584
    # one chip of sixteen: 12 held of a 192-wide router, 8 picks.  Of the
    # token step's 256 rows 105 pick a held expert and lie in 144: the
    # dense form over those; a chunk of 2,048 is grouped (its 928 rows
    # would be past where the dense form's operations hide); training,
    # float64 and the CPU stay dense
    share = (12, 192, 8, 7168, 2048)
    assert path(256, *share, jnp.bfloat16, False) == "held_rows"
    assert path(512, *share, jnp.bfloat16, False) == "held_rows"
    assert path(768, *share, jnp.bfloat16, False) == "grouped"
    assert path(2048, *share, jnp.bfloat16, False) == "grouped"
    assert path(64, *share, jnp.bfloat16, False) == "dense"
    assert path(128, *share, jnp.bfloat16, False) == "dense"
    assert path(256, *share, jnp.bfloat16, True) == "dense"
    assert path(256, *share, jnp.float64, False) == "dense"
    assert path(2048, 8, 192, 8, 7168, 2048, jnp.bfloat16, False) == "dense"
    # the held rows need no kernel: widths that do not tile keep them
    assert path(256, 12, 192, 8, 7168, 2000, jnp.bfloat16,
                False) == "held_rows"
    # the five decode cells' token steps: where an even router leaves
    # most held experts without a pick, the grouped form reads the picked
    # ones alone (command_a and keye: 16 of a 128-wide router, 8 tokens
    # of 8 picks, 0.403 of them picked; dots3: 8 of 256, 32 tokens,
    # 0.638); Xing4.0 holds every expert, A.X-K1's 256 tokens pick 1.0
    command_a, keye = (16, 128, 8, 4096, 4096), (16, 128, 8, 2048, 768)
    dots3 = (8, 256, 8, 5120, 1536)
    assert path(8, *command_a, jnp.bfloat16, False) == "grouped"
    assert path(8, *keye, jnp.bfloat16, False) == "grouped"
    assert path(32, *dots3, jnp.bfloat16, False) == "grouped"
    assert path(64, *cell, jnp.bfloat16, False) == "dense"
    assert path(256, *share, jnp.bfloat16, False) == "held_rows"
    assert [round(experts.picked_share(t, 8, n), 3) for t, n in (
        (8, 128), (32, 256), (64, 192), (128, 192))] == [
            0.403, 0.638, 0.934, 0.996]
    # the reckoned crossing takes the side the sweep measured (tokens,
    # the share's shape, whether the grouped form was the faster)
    for tokens, shape, faster in (
            (8, command_a, True), (40, command_a, True),
            (48, command_a, False), (64, command_a, False),
            (8, keye, True), (16, keye, False), (64, keye, False),
            (32, dots3, True), (48, dots3, True), (128, dots3, False)):
        held, n_experts, top_k, hidden, width = shape
        _, tile = experts.grouped_rows(tokens, top_k, held, n_experts)
        assert experts.grouped_step_wins(tokens, top_k, held, n_experts,
                                         hidden, width, 2, tile) is faster
    # and leaves A.X-K1's share dense at 64 tokens
    assert not experts.grouped_step_wins(64, 8, 12, 192, 7168, 2048, 2, 64)
    # chunks of those cells keep the grouped form; training, float64
    # and a share whose picks reach every held expert stay dense
    assert path(2048, *command_a, jnp.bfloat16, False) == "grouped"
    assert path(8, *command_a, jnp.bfloat16, True) == "dense"
    assert path(8, *command_a, jnp.float64, False) == "dense"
    assert path(64, *command_a, jnp.bfloat16, False) == "dense"
    # one sublane tile of 16 rows, and whole expert blocks or halves of
    # them a column tile at those rows
    for shape in ((8, 8, 16, 128), (32, 8, 8, 256)):
        assert experts.grouped_rows(*shape) == (16, 16)
    assert [experts.grouped_tile_columns(k, n, 2, 16) for k, n in (
        (4096, 4096), (2048, 768), (768, 2048), (5120, 1536),
        (1536, 5120))] == [2048, 768, 2048, 1536, 5120]
    monkeypatch.undo()
    assert path(256, *share, jnp.bfloat16, False) == "dense"


@pytest.mark.parametrize("shape,want", [
    # tokens, picks, held, the router's width -> the grouped form's rows
    # and rows a tile; the rows of the tokens that picked a held expert
    ((256, 8, 12, 192), (256, 256, 144)),   # A.X-K1's token step
    ((2048, 8, 12, 192), (2048, 128, 928)),     # its prefill chunk
    ((1984, 8, 12, 192), (1984 + 64, 128, 912)),
    ((2048, 4, 64, 64), (8192, 128, 2048)),     # Xing4.0's: every pair
    # rows few enough for one tile, in whole sublane tiles of 16
    ((64, 8, 12, 192), (64, 64, 48)),
    ((8, 2, 3, 4), (16, 16, 16)),           # never more than there are
    ((8, 8, 16, 128), (16, 16, 16)),        # command_a's and keye's steps
    ((32, 8, 8, 256), (16, 16, 32)),        # dots3's
    ((1, 8, 16, 128), (16, 16, 16)),
    ((512, 4, 48, 64), (2048, 128, 512)),
    ((256, 4, 16, 64), (512, 128, 208)),
])
def test_the_rows_are_a_bound_from_the_shapes(shape, want):
    assert (*experts.grouped_rows(*shape),
            experts.held_token_rows(*shape)) == want


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


@pytest.mark.parametrize("form", ["grouped", "held_rows"])
def test_a_share_serves_its_token_steps_by_either_form_and_counts_the_spills(
        monkeypatch, form):
    """Two of eight experts held, 12 rows: a token step's 24 pairs send
    6 here by an even router and lie in 16 rows (the grouped form), the
    tokens that sent them in 16 too (the dense form over the held
    rows), so as drawn no step spills.  With both held experts biased
    to win every token, and 8 rows for the held tokens, each step takes
    a second round and says so, once a layer a step, in
    ``moe_experts_spilled_total`` and in what ``generate`` returns; the
    logits are the reference's either way."""
    held = [6, 1]
    share = {**{k: v for k, v in CFG.items()
                if not k.startswith(("hc_", "mhc_"))},
             "n_routed_experts": 2, "published": {"n_routed_experts": 8}}
    force(monkeypatch, lambda tokens: form if tokens == 12 else "dense")
    ids = np.random.RandomState(2).randint(0, 256, (12, 6)).astype(np.int32)

    def spills():
        return monitor.counter("moe_experts_spilled_total", "").value(
            model="default", layer="L1_moe")

    # a net a phase (another seed: another program, whatever the store
    # holds): the rows are the program's, the bias a parameter
    for seed, bias, spilled in ((3, 0.0, 0), (4, 100.0, 3)):
        if spilled:
            monkeypatch.setattr(decoder, "held_token_rows", lambda *a: 8)
        net = ComputationGraph(from_config(
            share, dtype="float32", cache_len=16, init_std=0.1, seed=seed,
            experts_held=held)).init()
        net.params["L1_moe"]["router_bias"] = jnp.asarray(
            [bias if e in held else 0.0 for e in range(8)], jnp.float32)
        before, steps = spills(), _steps(form)
        with InferenceEngine(net, max_batch_size=16) as engine:
            engine.prefill_session("s", ids[:, :-1], chunk=5, cache_len=16)
            got = engine.generate("s", ids[:, -1:], 3)
        assert _steps(form) - steps == 3
        assert got.experts_spilled == {"L1_moe": spilled}
        assert spills() - before == spilled
        row = got.expert_tokens["L1_moe"]
        assert row.shape == (8,) and int(row.sum()) == 12 * 3 * 2
        assert (int(row[held].sum()) == 12 * 3 * 2) == bool(spilled)
        want = np.asarray(plain.forward(
            share, net.params, np.concatenate([ids, got.ids[:, :-1]], axis=1),
            experts_held=held))
        assert rel(np.stack(got.kept_logits), np.swapaxes(
            want[[0, -1], ids.shape[1] - 1:], 0, 1)) < 1e-5


@pytest.mark.parametrize("form", ["grouped", "dense"])
def test_the_read_counter_is_the_held_experts_a_step_picked(monkeypatch,
                                                            form):
    """``moe_experts_read_total``: 4 of a 24-wide router's experts held,
    12 rows of 2 picks a step (24 pairs, 16 rows of the held ones: the
    share's rows).  A grouped step reads the held experts that received
    a pick, counted here from the router's picks of that step alone (a
    call of one step: ``expert_tokens`` is that step's); a dense step
    reads every held expert."""
    held = [6, 1, 20, 13]
    share = {**{k: v for k, v in CFG.items()
                if not k.startswith(("hc_", "mhc_"))},
             "n_routed_experts": 4, "published": {"n_routed_experts": 24}}
    force(monkeypatch, lambda tokens: form if tokens == 12 else "dense")
    ids = np.random.RandomState(4).randint(0, 256, (12, 6)).astype(np.int32)
    net = ComputationGraph(from_config(
        share, dtype="float32", cache_len=16, init_std=0.1,
        seed={"grouped": 5, "dense": 6}[form], experts_held=held)).init()
    read = monitor.counter("moe_experts_read_total", "")
    reads = []
    with InferenceEngine(net, max_batch_size=16) as engine:
        engine.prefill_session("s", ids[:, :-1], chunk=5, cache_len=16)
        step = ids[:, -1:]
        for _ in range(4):
            before, steps = read.value(model="default",
                                       layer="L1_moe"), _steps(form)
            got = engine.generate("s", step, 1)
            step = got.ids[:, -1:]
            assert _steps(form) - steps == 1
            assert got.experts_spilled == {"L1_moe": 0}
            row = got.expert_tokens["L1_moe"]
            assert row.shape == (24,) and int(row.sum()) == 12 * 2
            picked = int((row[held] > 0).sum())
            reads.append(read.value(model="default", layer="L1_moe")
                         - before)
            assert reads[-1] == (picked if form == "grouped" else len(held))
    if form == "grouped":
        assert min(reads) < len(held)       # some held expert went unread
