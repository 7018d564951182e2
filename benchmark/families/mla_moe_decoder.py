"""Matrix products of the latent-attention, routed-expert,
hyper-connected decoder, from the published keys in a configuration's
file, for one token of decode at a given context; and the operations and
bytes of the two kernels whose share of their roofline the benchmark
reports.

Counted is the decode algorithm as it is served (the key half of
``kv_b`` absorbed into the query, scores and context against the latent
row): a multiply-add for every weight a token meets, ``top_k`` routed
experts and the shared ones, the attention's scores and context against
``context`` cached rows, the stream mixing's small products.  Norms,
softmax, Sinkhorn, routing's top-k and the sampling are not counted:
they are what MFU charges the step for.
"""

from typing import Dict, List, Optional, Sequence

BF16 = 2                                    # bytes


def _default_context(cfg: Dict) -> int:
    scaling = cfg.get("rope_scaling") or {}
    return int(scaling.get("original_max_position_embeddings", 4096))


def layers(cfg: Dict, context: Optional[float] = None) -> List[Dict]:
    """Forward multiply-adds of one generated token whose attention sees
    ``context`` cached rows (default: the configuration's original
    context length)."""
    s = float(_default_context(cfg) if context is None else context)
    c, n = cfg["hidden_size"], cfg["hc_mult"]
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    mixing = (n * c * (n + n + n * n)       # phi_pre, phi_post, phi_res
              + n * c + n * n * c + n * c)  # H_pre X, H_res X, H_post^T y
    attention = (c * rq + rq * h * (dn + dr) + c * (rkv + dr)
                 + h * dn * rkv             # the key half, absorbed
                 + h * s * (rkv + dr)       # scores
                 + h * s * rkv              # latent context
                 + h * rkv * dv + h * dv * c)
    dense = 3 * c * cfg["intermediate_size"]
    expert = 3 * c * cfg["moe_intermediate_size"]
    out = []
    for i in range(cfg["num_hidden_layers"]):
        out.append({"name": f"L{i}_attn", "kind": "latent_attention",
                    "macs": attention})
        if i < cfg["first_k_dense_replace"]:
            out.append({"name": f"L{i}_ffn", "kind": "gated_ffn",
                        "macs": dense})
        else:
            out.append({"name": f"L{i}_moe", "kind": "experts",
                        "macs": c * cfg["n_routed_experts"] + expert * (
                            cfg["num_experts_per_tok"]
                            + cfg["n_shared_experts"])})
        out.append({"name": f"L{i}_mixing", "kind": "hyper_connection",
                    "macs": 2 * mixing})
    out.append({"name": "head", "kind": "dense",
                "macs": c * cfg["vocab_size"]})
    return out


def moe_experts_kernel(cfg: Dict, tokens: int,
                       experts_touched: Sequence[float]) -> Dict:
    """Operations and bytes of the routed experts' matrix products (the
    ``layer.<vertex>.experts`` scope) for ONE step of ``tokens`` tokens:
    every token through its ``top_k`` experts; from HBM the matrices of
    the experts that received a token (``experts_touched``: one number
    an expert layer), the tokens in and the result out."""
    c, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    k = cfg["num_experts_per_tok"]
    flops = bytes_ = 0.0
    for touched in experts_touched:
        flops += 2.0 * tokens * k * 3 * c * f
        bytes_ += (touched * 3 * c * f + 2 * tokens * c) * BF16
    return {"flops": flops, "bytes": bytes_}


def mla_decode_kernel(cfg: Dict, rows: int, ring_slots: int,
                      new_tokens: int = 1) -> Dict:
    """Operations and bytes of the latent attention over the ring (the
    ``layer.<vertex>.latent_attention`` scope: the absorbed query, the
    scores, the softmax's input and the latent context) for ONE step of
    ``new_tokens`` positions a row, in every layer.  The dense masked
    form attends over the ring's capacity, so both are counted at
    ``ring_slots``; the ring is counted as read once."""
    h = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rkv = cfg["kv_lora_rank"]
    q = rows * new_tokens * h
    flops = 2.0 * q * (dn * rkv + ring_slots * (rkv + dr)
                       + ring_slots * rkv)
    bytes_ = (rows * ring_slots * (rkv + dr)        # both rings
              + rkv * h * dn                        # the absorbed half
              + q * (dn + dr + rkv)) * BF16         # queries in, context out
    n = cfg["num_hidden_layers"]
    return {"flops": n * flops, "bytes": float(n * bytes_)}
