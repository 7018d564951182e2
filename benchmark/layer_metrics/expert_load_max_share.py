"""The busiest expert's share of its layer's picks over the window, in
the worst layer: the growth of ``moe_expert_tokens_total{layer,expert}``
between the two snapshots.  ``100 / n_experts`` is even (1.56% for 64);
what the routed experts' products cost follows the experts touched, and
a deployment's balance follows this.  A program without the counter
reports nothing."""

import re

LAYER = "step program"
UNIT, BETTER, SOURCE = "%", "lower", "program_counter"
NAME = "moe_expert_tokens_total"


def read(record):
    def values(key):
        return ((record.get(key) or {}).get(NAME) or {}).get("values", {})
    before = values("monitor_before")
    layers = {}
    for labels, value in values("monitor_after").items():
        layer = re.search(r'layer="([^"]*)"', labels)
        grown = value - before.get(labels, 0.0)
        if layer and grown > 0:
            layers.setdefault(layer.group(1), []).append(grown)
    shares = [max(picks) / sum(picks) for picks in layers.values()]
    return 100.0 * max(shares) if shares else None
