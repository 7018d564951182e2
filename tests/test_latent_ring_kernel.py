"""The streamed latent attention (``ops.attention
.latent_ring_attention_streamed``) against the dense form on the CPU, in
Pallas interpret mode, at the published head geometry (32 heads, rank
512, rotary 64); the predicate that picks the form; the streamed form
forced through ``LatentAttention`` and the served path; and the kernel
compiled by Mosaic for a described v5e at the cell's shape, with the
scope it carries there (``tests/test_mla_moe_decoder.py`` holds the
token step's lowering to the same).  The experts' grouped product
(``ops.experts``, ``tests/test_grouped_experts.py``) is compiled for
the described v5e here too, and so are the two kernels of the indexed
sparse attention (``tests/test_gqa_sparse_decoder.py``): one file
describes the topology, so that one test worker loads the TPU's
compiler."""

import contextlib
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mla_moe_decoder as ref
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.models.mla_moe_decoder import from_config
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.ops import attention, experts
from deeplearning4j_tpu.serving import InferenceEngine

HEADS, RANK, ROPE = 32, 512, 64
CAPACITY, BLOCK = 256, 128          # two blocks a ring
SCALE = (128 + ROPE) ** -0.5


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def rings_and_queries(t, dtype, cursor, batch=2, seed=0):
    """Rings written up to ``cursor + t`` and garbage (finite, large)
    beyond, so that a slot read past the mask shows."""
    rng = np.random.RandomState(seed)
    draw = lambda *shape: rng.randn(*shape).astype(np.float32)
    c, r = draw(batch, CAPACITY, RANK), draw(batch, CAPACITY, ROPE)
    c[:, cursor + t:] = 1e4 * draw(batch, CAPACITY - cursor - t, RANK)
    r[:, cursor + t:] = 1e4 * draw(batch, CAPACITY - cursor - t, ROPE)
    q_lat, q_rope = draw(batch, t, HEADS, RANK), draw(batch, t, HEADS, ROPE)
    return tuple(jnp.asarray(a).astype(dtype)
                 for a in (0.3 * q_lat, q_rope, c, r))


@pytest.mark.parametrize("cursor", ["zero", "inside", "boundary", "last"])
@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("dtype,bound", [("float32", 1e-5),
                                         ("bfloat16", 8e-3)])
def test_streamed_agrees_with_dense(dtype, bound, t, cursor):
    """float32: the two forms differ by the order of their sums.
    bfloat16: by the rounding of ``p`` (the dense form rounds it
    normalized, the streamed one before the division: 2^-9 a term)."""
    cursor = {"zero": 0, "inside": 77, "boundary": BLOCK,
              "last": CAPACITY - t}[cursor]
    args = rings_and_queries(t, dtype, cursor)
    want = attention.latent_ring_attention_dense(*args, cursor,
                                                 sm_scale=SCALE)
    got = attention.latent_ring_attention_streamed(
        *args, jnp.asarray(cursor, jnp.int32), sm_scale=SCALE, block=BLOCK)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert rel(got, want) < bound


def test_blocks_beyond_the_cursor_are_not_read():
    """NaN in the second block: the dense form would spread it (0 x NaN),
    the streamed form neither fetches nor folds that block."""
    q_lat, q_rope, c, r = rings_and_queries(1, "float32", 5)
    clean = attention.latent_ring_attention_dense(q_lat, q_rope, c, r, 5,
                                                  sm_scale=SCALE)
    c, r = c.at[:, BLOCK:].set(jnp.nan), r.at[:, BLOCK:].set(jnp.nan)
    got = attention.latent_ring_attention_streamed(
        q_lat, q_rope, c, r, 5, sm_scale=SCALE, block=BLOCK)
    assert rel(got, clean) < 1e-5


def test_a_block_has_to_divide_the_ring():
    args = rings_and_queries(1, "float32", 0)
    with pytest.raises(ValueError, match="divides a ring of 256"):
        attention.latent_ring_attention_streamed(*args, 0, sm_scale=SCALE,
                                                 block=96)


# ------------------------------------------------------------ the predicate
def test_the_path_is_chosen_from_the_arguments(monkeypatch):
    path = attention.latent_ring_path
    cell = (1, HEADS, RANK, ROPE, 4096)
    # the CPU default: dense, whatever the arguments
    assert path(*cell, jnp.bfloat16) == "dense"
    monkeypatch.setattr(attention, "_mosaic", lambda: True)
    assert path(*cell, jnp.bfloat16) == "streamed"       # the token step
    assert path(*cell, jnp.float32) == "streamed"
    assert path(*cell, jnp.float64) == "dense"           # Mosaic has none
    assert path(32, HEADS, RANK, ROPE, 4096, jnp.bfloat16) == "streamed"
    # output() from a zero ring: the chunk is the ring
    assert path(4096, HEADS, RANK, ROPE, 4096, jnp.bfloat16) == "dense"
    assert path(128, 4, 32, 8, 128, jnp.float32) == "dense"
    # no block divides the ring; accumulators beyond VMEM; ragged rows
    assert path(1, HEADS, RANK, ROPE, 4000, jnp.bfloat16) == "dense"
    assert path(512, HEADS, RANK, ROPE, 4096, jnp.bfloat16) == "dense"
    assert path(1, 3, RANK, ROPE, 4096, jnp.bfloat16) == "dense"
    # the block shrinks as the rows grow, and stays a divisor
    blocks = [attention.latent_ring_block(t * HEADS, RANK, ROPE, 4096,
                                          jnp.bfloat16) for t in (1, 32, 64)]
    assert blocks == sorted(blocks, reverse=True) and blocks[0] >= 1024
    assert all(b and 4096 % b == 0 for b in blocks)


# ------------------------------------------------- through the layer, served
CFG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=2,
    first_k_dense_replace=1, intermediate_size=160,
    moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
    n_shared_experts=1, routed_scaling_factor=2.0, norm_topk_prob=True,
    num_attention_heads=8, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64,
                  "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"},
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)


@pytest.fixture
def streamed_net(monkeypatch):
    """A float32 decoder whose rings of 256 slots take the streamed form:
    the predicate is told that Mosaic is there; the kernel asks the real
    backend whether to interpret, and does.  With the net, the ``T`` of
    every trace of the streamed form."""
    kernel, calls = attention.latent_ring_attention_streamed, []

    def spy(*args, **kw):
        calls.append(args[0].shape[1])          # T
        return kernel(*args, **kw)

    monkeypatch.setattr(attention, "_mosaic", lambda: True)
    monkeypatch.setattr(attention, "latent_ring_attention_streamed", spy)
    net = ComputationGraph(from_config(
        CFG, dtype="float32", cache_len=256, init_std=0.1,
        hc_alpha_init=0.5, hc_bias_std=1.0, router_bias_std=0.2,
        seed=3)).init()
    return net, calls


def _steps(path, weights="stored"):
    return monitor.counter("latent_attention_steps_total", "").value(
        path=path, weights=weights)


def test_streamed_prefill_in_chunks_then_decode_agrees_with_the_full_forward(
        streamed_net):
    net, calls = streamed_net
    ids = np.random.RandomState(0).randint(0, 256, (3, 20)).astype(np.int32)
    want = np.asarray(ref.forward(CFG, net.params, ids))
    with InferenceEngine(net, max_batch_size=4) as engine:
        assert engine.prefill_session("snap", ids[:, :13], chunk=4,
                                      cache_len=256) == 13
        assert sorted(calls) == [1, 1, 4, 4]    # two layers, two shapes
        engine.fork_session("snap", "s")
        got = [engine.predict_session("s", ids[:, t:t + 1])
               for t in range(13, 20)]
        assert rel(np.stack(got, axis=1), want[:, 13:]) < 1e-5
        # counted on the host, once a launched step, by the same predicate
        streamed, dense = _steps("streamed"), _steps("dense")
        engine.fork_session("snap", "g")
        out = engine.generate("g", ids[:, 13:14], 3)
        assert (_steps("streamed"), _steps("dense")) == (streamed + 3, dense)
    assert rel(np.asarray(out.kept_logits[0]), want[[0, 2], 13]) < 1e-5
    # output() from a zero ring (T == capacity) keeps the dense form
    traced = len(calls)
    assert rel(net.output(ids), want) < 1e-5
    assert len(calls) == traced


def test_the_dense_default_counts_dense_steps():
    net = ComputationGraph(from_config(
        CFG, dtype="float32", cache_len=32, seed=3)).init()
    ids = np.random.RandomState(1).randint(0, 256, (2, 6)).astype(np.int32)
    streamed, dense = _steps("streamed"), _steps("dense")
    with InferenceEngine(net, max_batch_size=4) as engine:
        engine.prefill_session("s", ids[:, :-1], chunk=5, cache_len=32)
        engine.generate("s", ids[:, -1:], 4)
    assert (_steps("streamed"), _steps("dense")) == (streamed, dense + 4)


# -------------------------------------------------- the scope, and Mosaic
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _scoped(*args):
    with monitor.scope("layer", "L0_attn"), \
            monitor.subscope("latent_attention"):
        return attention.latent_ring_attention_streamed(
            *args, sm_scale=SCALE, interpret=False)


#: the two decode cells' shapes: rows, heads, ring slots, a prefill
#: chunk's positions a row; and their experts: hidden, width, held,
#: the router's width, picks a token
CELLS = {"xing4_29b_a4b": ((64, 32, 4096, 32), (3584, 1024, 64, 64, 4)),
         "ax_k1": ((256, 64, 1024, 8), (7168, 2048, 12, 192, 8))}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_mosaic_compiles_the_kernel_at_the_cells_shape(one_chip, cell):
    """The token step's shape (64 conversations, rings of 4,096, bf16;
    256 conversations of 64 heads over rings of 1,024) and a prefill
    chunk's (T = 32; T = 8, 512 rows of accumulators), compiled ahead of
    time for a described v5e: block shapes and VMEM are refused here,
    not on the chip, and the compiled kernel is one instruction that
    carries the scope it was called under.  Nothing runs.  (Without x64,
    which the tests turn on and Mosaic cannot take, and without the
    compile cache, which cannot read such an entry back.)"""
    from jax.experimental.compilation_cache import compilation_cache
    rows, heads, slots, chunk = CELLS[cell][0]
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            for t in (1, chunk):
                shapes = [
                    jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
                    for s in ((rows, t, heads, RANK), (rows, t, heads, ROPE),
                              (rows, slots, RANK), (rows, slots, ROPE))]
                cursor = jax.ShapeDtypeStruct((), jnp.int32,
                                              sharding=one_chip)
                text = jax.jit(_scoped).lower(
                    *shapes, cursor).compile().as_text()
                calls = [line for line in text.splitlines()
                         if 'custom_call_target="tpu_custom_call"' in line]
                assert len(calls) == 1
                # Mosaic's kernel is one instruction, named by its scope
                op_name = calls[0].split('op_name="')[1].split('"')[0]
                assert monitor.parse_op_name(op_name) == (
                    "layer.L0_attn.latent_attention", "forward")
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


@contextlib.contextmanager
def _without_compile_cache():
    """The persistent cache cannot read an executable of a described
    topology back: off around such compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def _one_layer_token_step(one_chip, cfg, rows, slots, weights):
    """The scheduled module of a one-layer net's token step, compiled
    for the described chip by ``tools/step_copies.py``."""
    from tools import step_copies
    with _without_compile_cache():
        net = step_copies.abstract_net(cfg, slots, one_chip, layers=1)
        return net, step_copies.compile_step(net, "token_step", rows, slots,
                                             1, one_chip, weights)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_served_token_step_turns_no_weight(one_chip, cell):
    """One decoder layer's token step at the cell's widths and rows,
    handed what a served net hands it (the forms laid once): the
    compiled module holds no ``copy`` that reads a matrix among the
    parameters, and all
    its copies that are kernels of their own read under 25 MB (what is
    left is the absorbed query turned rank-minor for the kernel, 16.8
    MB at 256 rows of 64 heads, and the rotary query, 2.1 MB).  Handed
    the stored parameters the same step turns both matrices every time
    (the parent's program: 77.6 MB a layer in ``ax_k1``)."""
    from tools import step_copies
    rows, _, slots, _ = CELLS[cell][0]
    with open(os.path.join(step_copies.ROOT, "benchmark", "configs",
                           f"{cell}.json")) as fh:
        cfg = json.load(fh)
    net, text = _one_layer_token_step(one_chip, cfg, rows, slots, "served")
    assert net.laid_vertices() == ["L0_attn"]
    assert step_copies.kernel_counts(text)["mosaic_kernels"] == 1
    alone = [r for r in step_copies.copies(text) if r["alone"]]
    # (a hyper-connection's one-number parameters go to scalar memory
    # by a copy of two bytes: not a weight turned)
    assert not [r for r in alone if r["parameter"].startswith("params[")
                and r["bytes"] > 64]
    assert sum(r["bytes"] for r in alone) < 25e6
    _, text = _one_layer_token_step(one_chip, cfg, rows, slots, "stored")
    turned = {r["parameter"] for r in step_copies.copies(text)
              if r["alone"] and r["parameter"].startswith("params[")}
    assert {"params['L0_attn']['Wqb']", "params['L0_attn']['Wkvb']"} <= turned


def test_step_copies_reads_a_toy_nets_steps(one_chip):
    """``tools/step_copies.py`` at toy widths: both steps of a two-layer
    net compile for the described chip, the kernel under each latent
    attention is there, and every ``copy`` comes back with its bytes,
    layouts, whether it is a kernel of its own and what it reads."""
    from tools import step_copies
    cfg = {"builder": "deeplearning4j_tpu.models.mla_moe_decoder:from_config",
           "container":
               "deeplearning4j_tpu.nn.computation_graph:ComputationGraph",
           "builder_args": {"init_std": 0.02},
           **{**CFG, "num_attention_heads": 8}}
    with _without_compile_cache():
        net = step_copies.abstract_net(cfg, 256, one_chip, layers=2)
        assert net.laid_vertices() == ["L0_attn", "L1_attn"]
        for step, weights in (("token_step", "served"),
                              ("prefill_step", "stored")):
            text = step_copies.compile_step(net, step, 8, 256, 8, one_chip,
                                            weights)
            counts = step_copies.kernel_counts(text)
            # a prefill chunk is run for the rings alone: nothing reads
            # the last layer's attention, only what it caches
            assert counts["mosaic_kernels"] == (2 if step == "token_step"
                                                else 1)
            assert counts["fusions"] > 0
            found = step_copies.copies(text)
            assert len(found) == counts["copies"]
            assert all(set(r) == {"bytes", "shape", "from", "to", "alone",
                                  "parameter", "op_name"} for r in found)
            assert found == sorted(found, key=lambda r: -r["bytes"])


def _scoped_experts(held, n_experts, *args):
    with monitor.scope("layer", "L1_moe"), monitor.subscope("experts"):
        return experts.grouped_experts(*args, held=list(range(held)),
                                       n_experts=n_experts, interpret=False)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_mosaic_compiles_the_grouped_experts_at_the_cells_widths(one_chip,
                                                                 cell):
    """A prefill chunk's 2,048 tokens (and a shorter remainder chunk's)
    through 64 experts of (3,584, 1,024), 4 picks, and through 12 held
    of 192 experts of (7,168, 2,048), 8 picks, and 256 tokens of that
    share (one tile of 256 rows), bf16: three kernels under
    the scope they were called under, the experts' matrices read where
    they lie: no instruction but the parameters has a matrix's shape,
    and the temporaries are the rows', nothing weight-sized; under a
    share the rows are those of the held pairs and nothing is as large
    as the pairs' rows would be."""
    from jax.experimental.compilation_cache import compilation_cache
    hidden, width, held, n_experts, top_k = CELLS[cell][1]
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    matrix = (f"bf16[{hidden},{held * width}]",
              f"bf16[{held * width},{hidden}]")
    try:
        with jax.enable_x64(False):
            for tokens in (2048, 1984) + (256,) * (held < n_experts):
                shapes = [
                    jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                    for s, d in (((tokens, hidden), jnp.bfloat16),
                                 ((tokens, top_k), jnp.int32),
                                 ((tokens, top_k), jnp.float32),
                                 ((hidden, held * width), jnp.bfloat16),
                                 ((hidden, held * width), jnp.bfloat16),
                                 ((held * width, hidden), jnp.bfloat16))]
                compiled = jax.jit(functools.partial(
                    _scoped_experts, held, n_experts)).lower(
                        *shapes).compile()
                lines = compiled.as_text().splitlines()
                calls = [line for line in lines
                         if 'custom_call_target="tpu_custom_call"' in line]
                # under a share the first round and the loop of the
                # further ones hold the three each
                assert len(calls) == (6 if held < n_experts else 3)
                assert {monitor.parse_op_name(
                    line.split('op_name="')[1].split('"')[0])
                    for line in calls} == {("layer.L1_moe.experts",
                                            "forward")}
                made = [line for line in lines if " = " in line
                        and line.split(" = ")[1].startswith(matrix)
                        and " parameter(" not in line
                        # a share's rounds are a loop; its body is
                        # handed the matrices, no copy of them
                        and " get-tuple-element(" not in line]
                assert not made, made
                # what a row holds between the kernels: the pair's row
                # in bf16, two float32 products and their bf16 product,
                # its float32 row out and, under a share, that row's
                # three bf16 terms and the tokens' float32 sums (235 MB
                # where every expert is held; 940 MB at 2,048 tokens of
                # the share before its pairs were compacted, now 16,384
                # pairs in 2,048 rows)
                rows, _ = experts.grouped_rows(tokens, top_k, held,
                                               n_experts)
                assert rows == (tokens * top_k if held == n_experts
                                else 2048 if tokens > 256 else 256)
                assert (compiled.memory_analysis().temp_size_in_bytes
                        < rows * (6 * hidden + 10 * width)
                        + (held < n_experts) * (rows * 6 + tokens * 8)
                        * hidden)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def _scoped_sparse(q, q_idx, w_idx, kv_ring, i_ring, cursor):
    with monitor.scope("layer", "L0_attn"):
        with monitor.subscope("indexer"):
            scores = attention.indexer_scores_streamed(
                q_idx, w_idx, i_ring, cursor, interpret=False)
        with monitor.subscope("select"):
            selected = attention.select_mask_streamed(scores, cursor, 2048,
                                                      interpret=False)
            if q.shape[1] == 1:
                slots, count = attention.selected_slots(selected[:, 0], 2048)
        with monitor.subscope("sparse_attention"):
            if q.shape[1] == 1:     # the token step fetches what it selected
                return attention.sparse_attention_gathered(
                    q, kv_ring, slots, count, sm_scale=128 ** -0.5,
                    interpret=False)
            return attention.sparse_attention_streamed(
                q, kv_ring, selected, cursor, sm_scale=128 ** -0.5,
                interpret=False)


def test_mosaic_compiles_the_sparse_kernels_at_the_cells_shape(one_chip):
    """``keye_vl2_30b_a3b.decode_b8_ctx32k``: 8 conversations, 32 query
    heads over 4 key/value heads of 128, an indexer of 16 heads of 64,
    rings of 32,768 slots, bf16; the token step's single position and a
    prefill chunk's 256, compiled ahead of time for a described v5e.
    Each of the three kernels (indexer, selection, attention: the token
    step's fetches its selected slots out of the ring in HBM by its own
    descriptors, the chunk's streams the ring) is one instruction under
    the scope it was called under."""
    from jax.experimental.compilation_cache import compilation_cache
    rows, slots = 8, 32768
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            for t in (1, 256):
                shapes = [
                    jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
                    for s in ((rows, t, 32, 128), (rows, t, 16, 64),
                              (rows, t, 16), (rows, slots, 8, 128),
                              (rows, slots, 64))]
                cursor = jax.ShapeDtypeStruct((), jnp.int32,
                                              sharding=one_chip)
                compiled = jax.jit(_scoped_sparse).lower(
                    *shapes, cursor).compile()
                calls = [line for line in compiled.as_text().splitlines()
                         if 'custom_call_target="tpu_custom_call"' in line]
                assert [monitor.parse_op_name(
                    c.split('op_name="')[1].split('"')[0])[0]
                    for c in calls] == ["layer.L0_attn.indexer",
                                        "layer.L0_attn.select",
                                        "layer.L0_attn.sparse_attention"]
                # float32 scores, the bfloat16 mask, and what the rare
                # tie's fallback keeps (keys, counts, a bool): nothing
                # is as large as a (heads, T, slots) array would be
                assert compiled.memory_analysis().temp_size_in_bytes \
                    < 32 * rows * t * slots
        assert attention.sparse_ring_block(slots) == 1024
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
