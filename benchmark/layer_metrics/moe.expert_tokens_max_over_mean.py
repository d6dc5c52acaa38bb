"""How unevenly a step's tokens fall on the held experts: the fullest
held expert's tokens (over the layers of the step) over the mean tokens
of a held expert that got any, median over the iterations of the traced
window. From the program's routing counts on its `serving.iteration`
spans (`moe_expert_tokens_max`, `moe_assignments_held`,
`moe_experts_touched`). 1 is even; the fullest expert sets how many
tiles its layer's kernel call takes."""

from benchmark import stats

META = {"layer": "expert layer", "unit": "x", "better": "lower",
        "source": "program_counter", "moves": "itl_p95_ms"}


def read(run):
    if run.traced is None:
        return None
    ratios = []
    for e in run.traced.spans:
        args = e.get("args") or {}
        if e.get("name") == "serving.iteration" \
                and args.get("moe_experts_touched"):
            mean = args["moe_assignments_held"] / args["moe_experts_touched"]
            ratios.append(args["moe_expert_tokens_max"] / mean)
    return stats.median(ratios) if ratios else None
