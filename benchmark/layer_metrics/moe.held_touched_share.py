"""Distinct (layer, held expert) pairs a fused step read, as a share of
the routed experts this chip HOLDS in its expert layers: the program's
``moe.step_expert_rows`` counter (counted over held experts, handed back
by the step itself) over ``num_experts`` (the experts held here) x expert
layers x fused steps. At a batch that gives each held expert the tokens a
deployment would, this is how much of the held expert weights a step
streams; lower is less traffic. A configuration whose router is no wider
than what it holds (no ``router_experts`` key), or a program without the
counter, reports nothing."""


def read(stats, spans, trace, cell):
    moe = stats.get("moe")
    steps = stats["batch"]["steps"]
    conf = cell["config"]
    if not moe or not steps or "router_experts" not in conf:
        return None
    layers = conf["num_hidden_layers"] - conf["first_k_dense_replace"]
    return 100.0 * moe["step_expert_rows"] / (
        conf["num_experts"] * layers * steps)
