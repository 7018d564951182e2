"""Matrix products of the decoder of latent attention whose layers are
full ones, attending over the rows a learned indexer selects, or a
window's, each gated headwise, with sigmoid-routed experts of which this
chip holds a share beside a shared expert, and a slice of the
vocabulary: from the published keys in a configuration's file, for one
token of decode at a given context; and the operations and bytes of the
kernels whose share of their roofline the benchmark reports.

Counted is the decode algorithm as it is served (the key half of
``Wkvb`` absorbed into the query, scores and context against the latent
rows), a multiply-add for every weight a token meets: in a
``full_attention`` layer the scores and context against the ``min(
context, index_topk)`` rows the indexer selects, and the indexer's
scores against every one of ``context`` cached keys; in a
``sliding_attention`` layer (the ``swa_`` sizes) against ``min(context,
sliding_window_size)`` rows; the gate's product in both; the router at
the SOURCE's width (``published.n_routed_experts``) while a token meets
``top_k x held / published`` of this chip's routed experts on average
and the shared one; the head at the slice's width.  Norms, rotary,
softmax, the selection and the sampling are not counted: they are what
MFU charges the step for.  A form that reads more than the algorithm
needs (rows fetched and written again before the kernel reads them, a
window ring's slots beyond the window) is charged for it by the roofline
share.
"""

from typing import Dict, List, Optional

from benchmark.families.mla_moe_share import (moe_experts_kernel,
                                              published_experts)

BF16 = 2                                    # bytes

__all__ = ["layers", "moe_experts_kernel", "indexer_kernel",
           "sparse_latent_attention_kernel", "window_latent_attention_kernel",
           "mla_decode_kernel", "published_experts", "layer_kinds"]


def layer_kinds(cfg: Dict) -> List[str]:
    """``layer_types`` of the layers that are run (the file keeps the
    source's list whole)."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _attention(cfg: Dict, kind: str):
    """(heads, q rank, kv rank, d_nope, d_rope, d_v) of a layer kind."""
    pre = "swa_" if kind == "sliding_attention" else ""
    return tuple(cfg[pre + k] for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))


def _indexed(cfg: Dict, kind: str) -> bool:
    return kind == "full_attention" and "index_n_heads" in cfg


def _indexer_matrices(cfg: Dict) -> int:
    """Weights of the indexer: ``WqI`` from the query latent, ``WkI``,
    ``Ww``."""
    c, rq = cfg["hidden_size"], cfg["q_lora_rank"]
    j, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return rq * j * di + c * di + c * j


def layers(cfg: Dict, context: Optional[float] = None) -> List[Dict]:
    """Forward multiply-adds of one generated token whose attention sees
    ``context`` cached rows (default: the configuration's
    ``max_position_embeddings``)."""
    s = float(cfg["max_position_embeddings"] if context is None else context)
    c = cfg["hidden_size"]
    width = published_experts(cfg)
    here = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / width
    expert = 3 * c * cfg["moe_intermediate_size"]
    out = []
    for i, kind in enumerate(layer_kinds(cfg)):
        h, rq, rkv, dn, dr, dv = _attention(cfg, kind)
        read = (min(s, cfg["sliding_window_size"])
                if kind == "sliding_attention" else min(s, cfg["index_topk"]))
        macs = (c * rq + rq * h * (dn + dr) + c * (rkv + dr)
                + h * dn * rkv              # the key half, absorbed
                + h * read * (rkv + dr)     # scores
                + h * read * rkv            # latent context
                + h * rkv * dv + h * dv * c
                + c * h)                    # the gate
        if _indexed(cfg, kind):
            macs += (_indexer_matrices(cfg)
                     + cfg["index_n_heads"] * s * cfg["index_head_dim"])
        out.append({"name": f"L{i}_attn", "kind": kind, "macs": macs})
        if i < cfg["first_k_dense_replace"]:
            out.append({"name": f"L{i}_ffn", "kind": "gated_ffn",
                        "macs": 3 * c * cfg["intermediate_size"]})
        else:
            out.append({"name": f"L{i}_moe", "kind": "experts",
                        "macs": c * width + expert * (
                            here + cfg["n_shared_experts"])})
    out.append({"name": "head", "kind": "dense",
                "macs": c * cfg["vocab_size"]})
    return out


def indexer_kernel(cfg: Dict, rows: int, slots: int) -> Dict:
    """Operations and bytes of the indexer (the ``layer.<vertex>.indexer``
    scope: its three projections of the new token, its scores against
    the ring) for ONE token step of ``rows`` conversations, in every
    full layer: the indexer-key ring at its capacity ``slots``, read
    once, and the three matrices; a multiply-add for every head, slot
    and number, and for every weight."""
    j, di = cfg["index_n_heads"], cfg["index_head_dim"]
    matrices = _indexer_matrices(cfg)
    n = sum(_indexed(cfg, k) for k in layer_kinds(cfg))
    flops = 2.0 * rows * (j * slots * di + matrices)
    bytes_ = (rows * slots * di + matrices) * BF16
    return {"flops": n * flops, "bytes": float(n * bytes_)}


def _latent_kernel(cfg: Dict, rows: int, visible: float, kind: str,
                   n: int) -> Dict:
    h, _, rkv, _, dr, _ = _attention(cfg, kind)
    return {"flops": n * 2.0 * rows * h * visible * (2 * rkv + dr),
            "bytes": float(n * rows * visible * (rkv + dr) * BF16)}


def sparse_latent_attention_kernel(cfg: Dict, rows: int,
                                   selected: float) -> Dict:
    """Operations and bytes of the attention over the selected latent
    rows (the ``layer.<vertex>.sparse_latent_attention`` scope: the rows
    taken, scores, softmax, latent context; the projections, the
    absorbed query and the value half lie outside it, under
    ``layer.<vertex>``) for ONE token step of ``rows`` conversations, in
    every full layer: ``selected`` joined latent rows a conversation,
    whatever the program read to get them."""
    n = sum(_indexed(cfg, k) for k in layer_kinds(cfg))
    return _latent_kernel(cfg, rows, selected, "full_attention", n)


def window_latent_attention_kernel(cfg: Dict, rows: int,
                                   visible: float) -> Dict:
    """The same for the window layers (the
    ``layer.<vertex>.window_latent_attention`` scopes): ``visible`` rows
    of the window's joined latent ring a conversation (the window, once
    the context has passed it), whatever slots the program read."""
    n = layer_kinds(cfg).count("sliding_attention")
    return _latent_kernel(cfg, rows, visible, "sliding_attention", n)


def mla_decode_kernel(cfg: Dict, rows: int, ring_slots: int,
                      new_tokens: int = 1) -> Dict:
    """What ``drivers/decode_sessions.py`` asks every decode family for
    by this name: here the full layers' attention at a full selection
    (``drivers/decode_sparse_window.py`` files its own counts)."""
    return sparse_latent_attention_kernel(
        cfg, rows * new_tokens, min(ring_slots, cfg["index_topk"]))
