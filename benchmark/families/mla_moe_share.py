"""Matrix products of the latent-attention, routed-expert decoder on a
plain residual path, of which this chip holds a share of the routed
experts and a slice of the vocabulary: from the published keys in a
configuration's file, for one token of decode at a given context; and
the operations and bytes of the two kernels whose share of their
roofline the benchmark reports.

Counted is the decode algorithm as it is served, as
``families/mla_moe_decoder.py`` counts it, with three differences: no
stream mixing; the router at the SOURCE's width
(``published.n_routed_experts``) while a token meets ``top_k x held /
published`` of this chip's routed experts on average (the rest of its
picks name experts held elsewhere, which this chip neither reads nor
computes) plus the shared ones; the head at the slice's width.  The
experts' kernel is counted for the held share alone: the matrices of
the HELD experts that received a token, the picks that landed on them.
The dense form multiplies every token by every held expert; what it
spends on the unchosen is not the algorithm's and is what the roofline
share charges it for.
"""

from typing import Dict, List, Optional, Sequence

from benchmark.families.mla_moe_decoder import (BF16, _default_context,
                                                mla_decode_kernel)

__all__ = ["layers", "moe_experts_kernel", "mla_decode_kernel",
           "published_experts"]


def published_experts(cfg: Dict) -> int:
    """The router's width: the source's expert count."""
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def layers(cfg: Dict, context: Optional[float] = None) -> List[Dict]:
    """Forward multiply-adds of one generated token whose attention sees
    ``context`` cached rows (default: the configuration's original
    context length)."""
    s = float(_default_context(cfg) if context is None else context)
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    attention = (c * rq + rq * h * (dn + dr) + c * (rkv + dr)
                 + h * dn * rkv             # the key half, absorbed
                 + h * s * (rkv + dr)       # scores
                 + h * s * rkv              # latent context
                 + h * rkv * dv + h * dv * c)
    dense = 3 * c * cfg["intermediate_size"]
    expert = 3 * c * cfg["moe_intermediate_size"]
    width = published_experts(cfg)
    here = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / width
    out = []
    for i in range(cfg["num_hidden_layers"]):
        out.append({"name": f"L{i}_attn", "kind": "latent_attention",
                    "macs": attention})
        if i < cfg["first_k_dense_replace"]:
            out.append({"name": f"L{i}_ffn", "kind": "gated_ffn",
                        "macs": dense})
        else:
            out.append({"name": f"L{i}_moe", "kind": "experts",
                        "macs": c * width + expert * (
                            here + cfg["n_shared_experts"])})
    out.append({"name": "head", "kind": "dense",
                "macs": c * cfg["vocab_size"]})
    return out


def moe_experts_kernel(cfg: Dict, tokens: int,
                       experts_touched: Sequence[float],
                       held_picks: Optional[Sequence[float]] = None
                       ) -> Dict:
    """Operations and bytes of the routed experts' matrix products (the
    ``layer.<vertex>.experts`` scope) for ONE step of ``tokens`` tokens:
    the picks that landed on a held expert (``held_picks``, one number
    an expert layer; default: the mean, ``tokens x top_k x held /
    published``) through that expert; from HBM the matrices of the held
    experts that received a token (``experts_touched``, one number a
    layer, counted over the held ids: never more than the layer holds),
    the tokens in and the result out."""
    c, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    if held_picks is None:
        held_picks = [tokens * cfg["num_experts_per_tok"] * held
                      / published_experts(cfg)] * len(experts_touched)
    flops = bytes_ = 0.0
    for touched, picks in zip(experts_touched, held_picks):
        flops += 2.0 * picks * 3 * c * f
        bytes_ += (touched * 3 * c * f + 2 * tokens * c) * BF16
    return {"flops": flops, "bytes": bytes_}
