"""Distinct (layer, expert) pairs a fused step read, as a share of all the
routed experts of its expert layers: the program's ``moe.step_expert_rows``
counter (handed back by the step itself) over ``n_routed_experts`` x expert
layers x fused steps. What a step's routed weights cost against reading
every expert; lower is less traffic. A program without the counter (no
experts, or a parent that lacks it) reports nothing."""


def read(stats, spans, trace, cell):
    moe = stats.get("moe")
    steps = stats["batch"]["steps"]
    if not moe or not steps:
        return None
    conf = cell["config"]
    layers = conf["num_hidden_layers"] - conf["first_k_dense_replace"]
    return 100.0 * moe["step_expert_rows"] / (
        conf["n_routed_experts"] * layers * steps)
