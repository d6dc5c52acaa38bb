"""The whole serving step's share of the chip's bf16 peak over the
WHOLE measured window: the forward operations the window's tokens needed
(`flops.decoder_step_flops` over the runner's request log: every fed
token, prompt or generated, through the layers' matrix products, every
streamed token through the head, every attention call at its live
context) over window x peak. It stands beside
`paged_attention_roofline`: a change that takes a kernel off the path
leaves that share silent and still has to show here. Padded columns of
the (lanes, chunk) grid are no work, so this reads a few per cent in a
cell whose grid is a sixth full (`fused_step.valid_column_share`)."""

from benchmark import flops

META = {"layer": "fused step", "unit": "%", "better": "higher",
        "source": "host_clock", "moves": "itl_p95_ms"}


def read(run):
    f = run.facts
    body = f.get("body_matmul_flops_per_token")
    head = f.get("head_matmul_flops_per_token")
    if not body or not head or run.ctx.peaks is None or not run.requests:
        return None
    calls = flops.lane_calls(run.requests, f["chunk"], run.t0, run.t1)
    ops = flops.decoder_step_flops(
        calls, f["window_tokens"], body, head, f["num_layers"],
        f["num_heads"], f["head_dim"])
    return 100.0 * ops / ((run.t1 - run.t0) * run.ctx.peaks["flops_per_s"]
                          * run.ctx.chips)
