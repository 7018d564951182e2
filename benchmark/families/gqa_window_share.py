"""Matrix products of the decoder of dense grouped-query attention whose
layers are a window's or full ones, with sigmoid-routed experts of which
this chip holds a share beside shared experts held whole, and a slice of
the vocabulary under a tied head: from the published keys in a
configuration's file, for one token of decode at a given context; and the
operations and bytes of the kernels whose share of their roofline the
benchmark reports.

Counted is the algorithm, whatever implements it: a multiply-add for
every weight a token meets; the attention's scores and context against
the rows a query sees, ``min(context, sliding_window)`` in a
``sliding_attention`` layer and ``context`` in a ``full_attention`` one;
the router at the SOURCE's width (``published.num_experts``) while a
token meets ``top_k x held / published`` of this chip's routed experts on
average and every shared one; the head at the slice's width.  Norms,
rotary, softmax and the sampling are not counted: they are what MFU
charges the step for.  A form that reads more than the algorithm needs
(a window ring's slots beyond the window) is charged for it by the
roofline share.
"""

from typing import Dict, List, Optional, Sequence

from benchmark.families import mla_moe_share

BF16 = 2                                    # bytes

__all__ = ["layers", "moe_experts_kernel", "window_attention_kernel",
           "full_attention_kernel", "mla_decode_kernel",
           "published_experts", "layer_kinds"]


def published_experts(cfg: Dict) -> int:
    """The router's width: the source's expert count."""
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def layer_kinds(cfg: Dict) -> List[str]:
    """``layer_types`` of the layers that are run (the file keeps the
    source's list whole)."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _sizes(cfg: Dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def layers(cfg: Dict, context: Optional[float] = None) -> List[Dict]:
    """Forward multiply-adds of one generated token whose attention sees
    ``context`` cached rows (default: the configuration's
    ``max_position_embeddings``)."""
    s = float(cfg["max_position_embeddings"] if context is None else context)
    c, h, g, d = _sizes(cfg)
    projections = c * h * d + 2 * c * g * d + h * d * c     # q, k, v, o
    width = published_experts(cfg)
    here = cfg["num_experts_per_tok"] * cfg["num_experts"] / width
    expert = 3 * c * cfg["intermediate_size"]
    out = []
    for i, kind in enumerate(layer_kinds(cfg)):
        read = min(s, cfg["sliding_window"]) \
            if kind == "sliding_attention" else s
        out.append({"name": f"L{i}_attn", "kind": kind,
                    "macs": projections + 2 * h * read * d})
        out.append({"name": f"L{i}_moe", "kind": "experts",
                    "macs": c * width + expert * (
                        here + cfg.get("num_shared_experts", 0))})
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
    ``drivers/decode_mixed.py`` drops the count and says why."""
    return mla_moe_share.moe_experts_kernel(
        {**cfg, "n_routed_experts": cfg["num_experts"],
         "moe_intermediate_size": cfg["intermediate_size"],
         "published": {"n_routed_experts": published_experts(cfg)}},
        tokens, experts_touched, held_picks)


def _attention_kernel(cfg: Dict, rows: int, visible: float,
                      kind: str) -> Dict:
    _, h, g, d = _sizes(cfg)
    n = layer_kinds(cfg).count(kind)
    return {"flops": n * 4.0 * rows * h * visible * d,
            "bytes": float(n * rows * visible * 2 * g * d * BF16)}


def window_attention_kernel(cfg: Dict, rows: int, visible: float) -> Dict:
    """Operations and bytes of the window layers' attention (the
    ``layer.<vertex>.window_attention`` scopes: scores, softmax, context;
    the four projections and the rotary lie outside them, under
    ``layer.<vertex>``) for ONE token step of ``rows`` conversations, in
    every ``sliding_attention`` layer: ``visible`` rows of keys and of
    values a conversation (the window, once the context has passed it),
    whatever slots of the ring the program read to get them."""
    return _attention_kernel(cfg, rows, visible, "sliding_attention")


def full_attention_kernel(cfg: Dict, rows: int, visible: float) -> Dict:
    """The same for the ``full_attention`` layers (the
    ``layer.<vertex>.full_attention`` scopes): ``visible`` is the
    context."""
    return _attention_kernel(cfg, rows, visible, "full_attention")


def mla_decode_kernel(cfg: Dict, rows: int, ring_slots: int,
                      new_tokens: int = 1) -> Dict:
    """What ``drivers/decode_sessions.py`` asks every decode family for
    by this name: here the full layers' attention at a full ring
    (``drivers/decode_mixed.py`` files its own counts under
    ``full_attention`` and ``window_attention``)."""
    return full_attention_kernel(cfg, rows * new_tokens, ring_slots)
