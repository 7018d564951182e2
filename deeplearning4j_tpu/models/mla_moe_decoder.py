"""A decoder of attention blocks with routed experts, on a plain or a
hyper-connected residual path, built on the ComputationGraph DSL.

One builder for the published ``config.json`` files it can read, the
sublayers picked from the file's own keys:

- attention: latent attention (``LatentAttention``) from DeepSeek-V3's
  ``kv_lora_rank`` and its companions; grouped-query attention over the
  rows a learned indexer selects (``SparseGroupedQueryAttention``) from
  ``sa_config`` with ``num_key_value_heads`` and ``head_dim``; dense
  grouped-query attention, a window's layers beside full ones
  (``GroupedQueryAttention``), from Cohere2's ``layer_types`` and
  ``sliding_window`` with ``num_key_value_heads`` and ``head_dim``
  (``sliding_attention``: the window and rotary over the interleaved
  pairs; ``full_attention``: neither);
- experts: DeepSeek-V3's keys (``n_routed_experts``,
  ``n_shared_experts``, ``routed_scaling_factor``, ``scoring_func``,
  ``first_k_dense_replace``), the Qwen3-MoE family's (``num_experts``,
  softmax scores, no shared expert, ``mlp_only_layers`` /
  ``decoder_sparse_step`` for the dense layers), or Cohere2-MoE's
  (``num_experts``, ``expert_selection_fn``, ``num_shared_experts``
  combined by ``shared_expert_combination_strategy``, an expert's width
  under ``intermediate_size``, ``first_k_dense_replace``); how a router
  scores is read from the file's own key where it has one
  (``scoring_func``, ``expert_selection_fn``) and is the family's
  otherwise;
- norms: RMS norms at ``rms_norm_eps``, or LayerNorms (a gain, no bias)
  at ``layer_norm_eps`` where the file gives that and no
  ``rms_norm_eps``;
- residual path: the hyper-connection keys (``hc_mult``,
  ``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min/max``), the
  parallel block (``use_parallel_block``), or none of them;
- head: a matrix of its own, or the embedding's table
  (``tie_word_embeddings``, times ``logit_scale``).

The benchmark's ``xing4_29b_a4b`` configuration is a latent-attention
file with the hyper-connection keys, ``ax_k1`` one without,
``keye_vl2_30b_a3b`` a ``sa_config`` file, ``command_a_plus_05_2026`` a
``layer_types`` file with the parallel block; the CPU tests run a small
one of each.  A key the builder would have to understand and does not
(``use_qk_norm``, ``attention_bias``, a rotary it has no form for, a
kind of layer it does not make) raises and names the key.

With ``hc_mult``, per token: ``embed`` -> ``streams`` (``hc_mult``
copies) -> for each layer ``L<i>`` two sublayers, attention then
feed-forward, each wrapped as ``_read`` (the streams' part the sublayer
sees) -> ``_norm`` -> the sublayer (``L<i>_attn``; ``L<i>_ffn`` dense
for the first ``first_k_dense_replace`` layers, ``L<i>_moe`` after) ->
``_write`` (streams mixed, the output added) -> ``stream_sum`` ->
``final_norm`` -> ``head`` (float32 logits).

Without it the residual path is the plain pre-norm one, ``x + F(rmsnorm
(x))``: ``embed`` -> for each layer ``L<i>_attn_norm`` -> ``L<i>_attn``
-> ``L<i>_attn_add``, ``L<i>_ffn_norm`` -> ``L<i>_ffn`` | ``L<i>_moe``
-> ``L<i>_ffn_add`` -> ``final_norm`` -> ``head``: one stream, no
``streams`` / ``stream_sum``, no Sinkhorn; the sublayers keep their
names.  Under ``use_parallel_block`` a layer has ONE norm that feeds both
sublayers, ``x + Attn(norm(x)) + F(norm(x))``: ``L<i>_norm`` ->
(``L<i>_attn``, ``L<i>_ffn`` | ``L<i>_moe``) -> ``L<i>_add`` (three
inputs: the stream and both outputs).

A file that is one chip's share of an expert-parallel deployment gives
the experts it holds under ``n_routed_experts`` (``num_experts``) and
the source's count under ``published``: the router keeps the published
width, and ``experts_held`` says which of its outputs are the held
experts.

The multi-token-prediction module of a published model is not built: it
is not part of the served forward pass; nor is a vision tower.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from ..nn.conf.computation_graph import (ElementWiseVertex,
                                         StreamExpandVertex, StreamSumVertex)
from ..nn.conf.neural_net_configuration import NeuralNetConfiguration
from ..nn.layers.decoder import (GatedFeedForward, GroupedQueryAttention,
                                 HyperConnectionRead, HyperConnectionWrite,
                                 LatentAttention, LayerNorm, LMHead,
                                 MixtureOfExperts, RMSNorm,
                                 SparseGroupedQueryAttention, TiedLMHead,
                                 TokenEmbedding)
from ..nn.weights import Distribution


def from_config(cfg: Dict, *, cache_len: int = 4096,
                experts_held: Optional[List[int]] = None,
                init_std: float = 0.02, hc_alpha_init: float = 0.01,
                hc_bias_std: float = 0.0, router_bias_std: float = 0.0,
                max_chunk: int = 256,
                dtype: Optional[str] = None, seed: int = 0):
    """The graph configuration for the published keys in ``cfg``.
    ``cache_len`` is the default capacity of the attention rings that
    grow (a window layer's ring is sized by its window and
    ``max_chunk``, the most positions one call brings it);
    ``experts_held`` the experts every expert layer holds (default:
    all); ``init_std`` the normal deviation of every matrix;
    ``hc_alpha_init``, ``hc_bias_std`` and ``router_bias_std`` the
    initial values the source does not give (a hyper-connection's
    ``alpha`` and the deviation of its ``b``, read only where ``cfg``
    has ``hc_mult``; the deviation of the router's selection bias);
    ``dtype`` the parameter dtype (default: the backend's policy)."""
    b = (NeuralNetConfiguration.builder().seed(seed).updater("sgd")
         .weight_init("distribution")
         .dist(Distribution(kind="normal", std=float(init_std)))
         .activation("identity"))
    if dtype:
        b = b.dtype(dtype)
    g = b.graph_builder()
    c = int(cfg["hidden_size"])
    # three families' keys for the same things: DeepSeek-V3's, whose
    # router scores with a sigmoid unless ``scoring_func`` says
    # otherwise; Qwen3-MoE's (``num_experts``), with a softmax; and
    # Cohere2-MoE's (``num_experts`` too), which names its scoring
    # itself (``expert_selection_fn``)
    cohere = "expert_selection_fn" in cfg
    qwen = "num_experts" in cfg and not cohere
    experts_key = "n_routed_experts" if "n_routed_experts" in cfg \
        else "num_experts"
    for key, makes in (("use_qk_norm", "no norm on q and k"),
                       ("attention_bias", "no bias")):
        if cohere and cfg.get(key):
            raise ValueError(f"{key} is set: this builder's dense "
                             f"grouped-query attention has {makes}")
    # a share's file: the router's width is the source's count, the
    # file's own count how many of its experts this chip holds
    n_experts = int(cfg[experts_key])
    published = cfg.get("published", {}).get(experts_key)
    if published is not None:
        if len(experts_held or ()) != n_experts:
            raise ValueError(
                f"the file holds {n_experts} of {published} routed experts; "
                f"experts_held has to name them, not {experts_held}")
        n_experts = int(published)

    def norm():
        if cfg.get("rms_norm_eps") is None and "layer_norm_eps" in cfg:
            return LayerNorm(n_out=c, eps=float(cfg["layer_norm_eps"]))
        return RMSNorm(n_out=c, eps=float(cfg["rms_norm_eps"]))

    streams = "hc_mult" in cfg
    if streams:
        n = int(cfg["hc_mult"])
        hc = dict(n_in=c, n_streams=n, eps=float(cfg["hc_eps"]),
                  alpha_init=hc_alpha_init, bias_std=hc_bias_std)

        def sublayer(prefix: str, layer, stream: str, kind: str) -> str:
            g.add_layer(f"{prefix}_read", HyperConnectionRead(**hc), stream)
            g.add_layer(f"{prefix}_norm", norm(), f"{prefix}_read")
            name = f"{prefix.split('_')[0]}_{kind}"
            g.add_layer(name, layer, f"{prefix}_norm")
            g.add_layer(f"{prefix}_write", HyperConnectionWrite(
                sinkhorn_iters=int(cfg["hc_sinkhorn_iters"]),
                clamp_min=float(cfg["mhc_h_res_clamp_min"]),
                clamp_max=float(cfg["mhc_h_res_clamp_max"]), **hc),
                stream, name)
            return f"{prefix}_write"
    elif cfg.get("use_parallel_block"):
        def sublayer(prefix: str, layer, stream: str, kind: str) -> str:
            # one norm a layer feeds both sublayers; the second one
            # closes the block: the stream and both outputs added
            block = prefix.split("_")[0]
            name = f"{block}_{kind}"
            if prefix.endswith("_attn"):
                g.add_layer(f"{block}_norm", norm(), stream)
                g.add_layer(name, layer, f"{block}_norm")
                return stream
            g.add_layer(name, layer, f"{block}_norm")
            g.add_vertex(f"{block}_add", ElementWiseVertex(op="add"),
                         stream, f"{block}_attn", name)
            return f"{block}_add"
    else:
        def sublayer(prefix: str, layer, stream: str, kind: str) -> str:
            g.add_layer(f"{prefix}_norm", norm(), stream)
            name = f"{prefix.split('_')[0]}_{kind}"
            g.add_layer(name, layer, f"{prefix}_norm")
            g.add_vertex(f"{prefix}_add", ElementWiseVertex(op="add"),
                         stream, name)
            return f"{prefix}_add"

    g.add_inputs("ids")
    g.add_layer("embed", TokenEmbedding(n_in=int(cfg["vocab_size"]),
                                        n_out=c), "ids")
    x = "embed"
    if streams:
        g.add_vertex("streams", StreamExpandVertex(n_streams=n), x)
        x = "streams"
    def attention(i: int):
        common = dict(n_in=c, n_out=c,
                      n_heads=int(cfg["num_attention_heads"]),
                      rope_theta=float(cfg["rope_theta"]),
                      cache_len=int(cache_len))
        if "layer_types" in cfg and "sa_config" not in cfg \
                and "kv_lora_rank" not in cfg:
            kind = cfg["layer_types"][i]
            if kind not in ("sliding_attention", "full_attention"):
                raise ValueError(f"layer_types[{i}] is {kind!r}: this "
                                 "builder makes sliding_attention and "
                                 "full_attention")
            rope = cfg.get("position_embedding_type", "rope_gptj")
            if rope != "rope_gptj" or float(cfg.get("rotary_pct", 1)) != 1:
                raise ValueError(
                    f"position_embedding_type {rope!r} at rotary_pct "
                    f"{cfg.get('rotary_pct', 1)}: the window layers turn "
                    "the whole head over the interleaved pairs (rope_gptj)")
            sliding = kind == "sliding_attention"
            return GroupedQueryAttention(
                n_kv_heads=int(cfg["num_key_value_heads"]),
                head_dim=int(cfg["head_dim"]),
                window=int(cfg["sliding_window"]) if sliding else None,
                rotary=sliding, chunk=int(max_chunk), **common)
        if "kv_lora_rank" in cfg:
            return LatentAttention(
                eps=float(cfg["rms_norm_eps"]),
                q_rank=int(cfg["q_lora_rank"]),
                kv_rank=int(cfg["kv_lora_rank"]),
                d_nope=int(cfg["qk_nope_head_dim"]),
                d_rope=int(cfg["qk_rope_head_dim"]),
                d_v=int(cfg["v_head_dim"]),
                rope_scaling=cfg.get("rope_scaling"), **common)
        if "sa_config" in cfg:
            sa = cfg["sa_config"]
            if int(sa.get("indexer_num_kv_heads", 1)) != 1:
                raise ValueError("the indexer caches one key head a token")
            return SparseGroupedQueryAttention(
                eps=float(cfg["rms_norm_eps"]),
                n_kv_heads=int(cfg["num_key_value_heads"]),
                head_dim=int(cfg["head_dim"]),
                index_heads=int(sa["indexer_num_heads"]),
                index_dim=int(sa["indexer_head_dim"]),
                topk=int(sa["topk"]), **common)
        raise ValueError("the file names no attention this builder makes: "
                         "none of kv_lora_rank, sa_config and layer_types")

    def dense(i: int) -> bool:
        if qwen:
            return (i in cfg.get("mlp_only_layers", ())
                    or (i + 1) % int(cfg.get("decoder_sparse_step", 1)) != 0)
        return i < int(cfg["first_k_dense_replace"])

    if cohere:
        combine = cfg.get("shared_expert_combination_strategy", "sum")
        if combine not in ("sum", "average"):
            raise ValueError(f"shared_expert_combination_strategy is "
                             f"{combine!r}: shared experts combine by "
                             "'sum' or 'average'")
        shared = dict(
            width=int(cfg["intermediate_size"]),
            n_shared=int(cfg.get("num_shared_experts", 0)),
            scoring=cfg["expert_selection_fn"], shared_combine=combine)
        dense_width = int(cfg.get("prefix_dense_intermediate_size",
                                  cfg["intermediate_size"]))
    else:
        shared = dict(
            width=int(cfg.get("moe_intermediate_size", 0)),
            n_shared=int(cfg.get("n_shared_experts", 0)),
            scoring=cfg.get("scoring_func",
                            "softmax" if qwen else "sigmoid"))
        dense_width = int(cfg.get("intermediate_size", 0))

    for i in range(int(cfg["num_hidden_layers"])):
        x = sublayer(f"L{i}_attn", attention(i), x, "attn")
        if dense(i):
            x = sublayer(f"L{i}_ffn", GatedFeedForward(
                n_in=c, n_out=c, width=dense_width), x, "ffn")
        else:
            x = sublayer(f"L{i}_ffn", MixtureOfExperts(
                n_in=c, n_out=c, n_experts=n_experts,
                top_k=int(cfg["num_experts_per_tok"]),
                routed_scaling=float(cfg.get("routed_scaling_factor", 1.0)),
                norm_topk=bool(cfg["norm_topk_prob"]),
                router_bias_std=router_bias_std,
                experts_held=experts_held, **shared),
                x, "moe")
    if streams:
        g.add_vertex("stream_sum", StreamSumVertex(), x)
        x = "stream_sum"
    g.add_layer("final_norm", norm(), x)
    if cfg.get("tie_word_embeddings"):
        head = TiedLMHead(n_in=c, n_out=int(cfg["vocab_size"]),
                          tied_to="embed",
                          logit_scale=float(cfg.get("logit_scale", 1.0)))
    else:
        head = LMHead(n_in=c, n_out=int(cfg["vocab_size"]))
    g.add_layer("head", head, "final_norm")
    g.set_outputs("head")
    return g.build()
