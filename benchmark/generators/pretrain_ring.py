"""Generator `pretrain_ring`: the ring of seeded host batches a
pre-training run cycles through. A traffic mix that names it is a data
file with `seq_len`, `ring` (how many batches), `log_every` and
`warmup_steps`; the configuration gives the vocabulary sizes and
`max_predictions_per_seq`, the runner the rows per step."""

import numpy as np

_SEED_MASK = 0xFFFFFFFF        # --seed may pass 2**31; numpy wants u32


def batch(cfg, seq_len, rows, seed):
    """One synthetic MLM + NSP batch in the feed schema of
    `models/bert.py:build_pretrain_net` (copied from that module's
    make_pretrain_feed, so that the inputs are the benchmark's own):
    uniform token ids, the first `max_predictions_per_seq` positions of
    each row masked, every position attended."""
    rs = np.random.default_rng([int(seed) & _SEED_MASK, 5])
    p = int(cfg["max_predictions_per_seq"])
    vocab = int(cfg["vocab_size"])
    return {
        "src_ids": rs.integers(0, vocab, (rows, seq_len)).astype(np.int64),
        "sent_ids": rs.integers(0, int(cfg["type_vocab_size"]),
                                (rows, seq_len)).astype(np.int64),
        "input_mask": np.ones((rows, seq_len), np.float32),
        "mask_pos": np.stack([np.arange(p) + i * seq_len
                              for i in range(rows)]).astype(np.int64),
        "mask_label": rs.integers(0, vocab, (rows, p)).astype(np.int64),
        "mask_weight": np.ones((rows, p), np.float32),
        "nsp_label": rs.integers(0, 2, (rows, 1)).astype(np.int64),
    }


def batches(params, cfg, rows, seed):
    """The ring: `ring` batches, each from a seed of its own."""
    base = (int(seed) & _SEED_MASK) * 1000003
    return [batch(cfg, int(params["seq_len"]), rows, base + i)
            for i in range(int(params["ring"]))]
