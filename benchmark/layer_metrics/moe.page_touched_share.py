"""Distinct (layer, expert) pairs a page program read, as a share of the
experts it could have read: the program's ``moe.page_expert_rows`` counter
(handed back by each page program, counted over held experts) over the
experts held (``num_experts``) x expert layers x page programs
(``moe.page_count``). How much of prefill goes to reading expert weights
for one page of tokens; a chunk of several pages would lower it. Expert
layers are the configuration's ``mlp_layer_types`` entries that are
``sparse`` among its first ``num_hidden_layers``. A program without the
counter, a window with no page program, or a configuration without
``mlp_layer_types``, reports nothing."""


def read(stats, spans, trace, cell):
    moe = stats.get("moe")
    conf = cell["config"]
    if not moe or not moe["page_count"] or "mlp_layer_types" not in conf:
        return None
    layers = sum(t == "sparse"
                 for t in conf["mlp_layer_types"][:conf["num_hidden_layers"]])
    return 100.0 * moe["page_expert_rows"] / (
        conf["num_experts"] * layers * moe["page_count"])
