"""Median share of the KV pool's blocks that hold live tokens over the
window: gauge `serving.blocks_in_use`, sampled by the runner every 50 ms,
over the pool's blocks. Reserved and unused memory limits the batch."""

from benchmark import stats

META = {"layer": "KV cache", "unit": "%", "better": "higher",
        "source": "program_counter", "moves": "output_tokens_per_s"}


def read(run):
    samples = run.samples.get("blocks_in_use")
    blocks = run.facts.get("pool_blocks")
    if not samples or not blocks:
        return None
    return 100.0 * stats.median(samples) / blocks
