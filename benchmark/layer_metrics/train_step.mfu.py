"""Model FLOP/s utilization: forward + backward matrix-product
operations per token (3 x the forward count of `benchmark/flops.py`
over the `Program`, no recompute counted) x the run's
`train_tokens_per_s`, over the chip's published bf16 peak."""

META = {"layer": "training step", "unit": "%", "better": "higher",
        "source": "host_clock", "moves": "train_tokens_per_s"}


def read(run):
    rate = run.e2e.get("train_tokens_per_s")
    per_token = run.facts.get("forward_matmul_flops_per_token")
    if not rate or not per_token or run.ctx.peaks is None:
        return None
    return 100.0 * 3 * per_token * rate / (
        run.ctx.peaks["flops_per_s"] * run.ctx.chips)
