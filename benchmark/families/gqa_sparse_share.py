"""Matrix products of the decoder of grouped-query attention over the
rows a learned indexer selects, with softmax-routed experts of which
this chip holds a share and a slice of the vocabulary: from the
published keys in a configuration's file, for one token of decode at a
given context; and the operations and bytes of the kernels whose share
of their roofline the benchmark reports.

Counted is the algorithm, whatever implements it: a multiply-add for
every weight a token meets; the indexer's scores against every one of
``context`` cached rows; the attention's scores and context against the
``min(context, topk)`` rows it selects; the router at the SOURCE's width
(``published.num_experts``) while a token meets ``top_k x held /
published`` of this chip's routed experts on average (there is no shared
one); the head at the slice's width.  Norms, rotary, softmax, the
selection itself and the sampling are not counted: they are what MFU
charges the step for.  A form that reads more than the algorithm needs
(the whole ring through a mask where 2,048 rows would do) is charged
for it by the roofline share.
"""

from typing import Dict, List, Optional, Sequence

from benchmark.families import mla_moe_share

BF16 = 2                                    # bytes

__all__ = ["layers", "moe_experts_kernel", "indexer_kernel",
           "sparse_attention_kernel", "mla_decode_kernel",
           "published_experts"]


def published_experts(cfg: Dict) -> int:
    """The router's width: the source's expert count."""
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def _sizes(cfg: Dict):
    sa = cfg["sa_config"]
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])


def layers(cfg: Dict, context: Optional[float] = None) -> List[Dict]:
    """Forward multiply-adds of one generated token whose attention sees
    ``context`` cached rows (default: the configuration's
    ``max_position_embeddings``)."""
    s = float(cfg["max_position_embeddings"] if context is None else context)
    c, h, g, d, j, di, topk = _sizes(cfg)
    read = min(s, topk)
    attention = (c * h * d + 2 * c * g * d      # q, k, v
                 + 2 * h * read * d             # scores, context
                 + h * d * c)                   # o
    indexer = c * j * di + c * di + c * j + j * s * di
    width = published_experts(cfg)
    here = cfg["num_experts_per_tok"] * cfg["num_experts"] / width
    expert = 3 * c * cfg["moe_intermediate_size"]
    out = []
    for i in range(cfg["num_hidden_layers"]):
        out.append({"name": f"L{i}_attn", "kind": "sparse_attention",
                    "macs": attention + indexer})
        out.append({"name": f"L{i}_moe", "kind": "experts",
                    "macs": c * width + expert * here})
    out.append({"name": "head", "kind": "dense",
                "macs": c * cfg["vocab_size"]})
    return out


def moe_experts_kernel(cfg: Dict, tokens: int,
                       experts_touched: Sequence[float],
                       held_picks: Optional[Sequence[float]] = None
                       ) -> Dict:
    """``mla_moe_share.moe_experts_kernel`` under this family's keys:
    the picks that landed on a held expert through that expert; from HBM
    the matrices of the held experts that received a token, the tokens
    in and the result out.  ``drivers/decode_sessions.py`` asks for it;
    ``drivers/decode_sparse.py`` drops the count and says why."""
    return mla_moe_share.moe_experts_kernel(
        {**cfg, "n_routed_experts": cfg["num_experts"],
         "published": {"n_routed_experts": published_experts(cfg)}},
        tokens, experts_touched, held_picks)


def indexer_kernel(cfg: Dict, rows: int, slots: int) -> Dict:
    """Operations and bytes of the indexer (the ``layer.<vertex>.indexer``
    scope: its three projections of the new token, its scores against
    the ring) for ONE token step of ``rows`` conversations, in every
    layer: the indexer-key ring at its capacity ``slots``, read once,
    and the three matrices; a multiply-add for every head, slot and
    lane."""
    c, _, _, _, j, di, _ = _sizes(cfg)
    flops = 2.0 * rows * j * slots * di
    bytes_ = (rows * slots * di + c * (j * di + di + j)) * BF16
    n = cfg["num_hidden_layers"]
    return {"flops": n * flops, "bytes": float(n * bytes_)}


def sparse_attention_kernel(cfg: Dict, rows: int, selected: int) -> Dict:
    """Operations and bytes of the attention over the selected rows (the
    ``layer.<vertex>.sparse_attention`` scope: the selection's mask,
    scores, softmax, context; the four projections lie outside it, under
    ``layer.<vertex>``) for ONE token step of ``rows`` conversations, in
    every layer: ``selected`` rows of the key and of the value ring a
    conversation."""
    _, h, g, d, _, _, _ = _sizes(cfg)
    flops = 4.0 * rows * h * selected * d
    bytes_ = rows * selected * 2 * g * d * BF16
    n = cfg["num_hidden_layers"]
    return {"flops": n * flops, "bytes": float(n * bytes_)}


def mla_decode_kernel(cfg: Dict, rows: int, ring_slots: int,
                      new_tokens: int = 1) -> Dict:
    """What ``drivers/decode_sessions.py`` asks every decode family for
    by this name: here the sparse attention's counts at a full
    selection (``drivers/decode_sparse.py`` files them under
    ``sparse_attention``)."""
    return sparse_attention_kernel(
        cfg, rows * new_tokens, min(ring_slots, cfg["sa_config"]["topk"]))
