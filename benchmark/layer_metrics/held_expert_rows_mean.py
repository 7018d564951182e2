"""Tokens a held expert sees a token step, averaged over the window's
steps, the expert layers and the experts they hold: the growth of
``moe_held_picks_total{layer}`` between the two snapshots over the
window's token steps (``record["steps"]``) times the held experts
(gauge ``moe_experts_held{layer}``, summed over the layers).  What a
share's cell is sized by: each held expert should see the tokens it
would see in the deployment (``rows x top_k / published experts`` under
even routing).  A program without the counter reports nothing."""

LAYER = "step program"
UNIT, BETTER, SOURCE = "count", "higher", "program_counter"


def read(record):
    def values(key, name):
        return ((record.get(key) or {}).get(name) or {}).get("values", {})
    before = values("monitor_before", "moe_held_picks_total")
    picks = sum(v - before.get(labels, 0.0) for labels, v in
                values("monitor_after", "moe_held_picks_total").items())
    held = sum(values("monitor_after", "moe_experts_held").values())
    steps = record.get("steps") or 0
    if picks <= 0 or held <= 0 or steps <= 0:
        return None
    return picks / (held * steps)
