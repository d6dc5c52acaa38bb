"""Compile-plane report table: per-program FLOPs / bytes / peak HBM /
compile ms / recompile causes.

The data comes from ``Executor.explain(program, feed)``
(docs/observability.md "Compile & memory"). Two modes:

    python tools/compile_report.py --demo [--out-dir DIR]
    python tools/compile_report.py --from DIR/compile_report_demo.json

``--from`` renders a saved JSON file: what ``--demo --out-dir`` wrote,
one ``explain()`` report, or a list of them. ``--demo`` builds a tiny GPT
train program on the CPU backend, drives an unbucketed-shape stream
past the recompile-storm threshold, calls explain(), and prints the
table plus the storm summary — the 60-second smoke of the whole
compile observatory.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _si(n, unit=""):
    if n is None:
        return "-"
    n = float(n)
    for div, suf in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(n) >= div:
            return f"{n / div:.2f}{suf}{unit}"
    return f"{n:.0f}{unit}"


def print_report_table(reports, file=None):
    """One row per explain() report: program | flops | bytes | peak HBM
    | compile ms | recompiles (cause of the latest one)."""
    out = file or sys.stdout
    hdr = (f"{'program':28s} {'flops':>10s} {'bytes':>10s} "
           f"{'peak HBM':>10s} {'compile ms':>11s} {'src':>6s}  recompiles")
    print(hdr, file=out)
    print("-" * len(hdr), file=out)
    for r in reports:
        comp = r.get("compile_ms") or {}
        comp_ms = f"{comp['avg']:.1f}" if comp.get("count") else "-"
        recs = r.get("recompiles") or []
        cause = f"{len(recs)} ({recs[-1]['summary']})" if recs else "0"
        src = r.get("source", {}).get("flops", "?")
        print(f"{r.get('program', '?'):28s} {_si(r.get('flops')):>10s} "
              f"{_si(r.get('bytes_accessed'), 'B'):>10s} "
              f"{_si(r.get('peak_hbm_bytes'), 'B'):>10s} "
              f"{comp_ms:>11s} {src:>6s}  {cause}",
              file=out)


def print_memory_summary(snapshot, file=None):
    """HBM-ledger rollup (the /memory endpoint body)."""
    out = file or sys.stdout
    print(f"hbm ledger: {_si(snapshot.get('total_bytes'), 'B')} resident "
          f"across {len(snapshot.get('entries', []))} entries", file=out)
    for comp, kinds in sorted(snapshot.get("by_component", {}).items()):
        parts = ", ".join(f"{k}={_si(v, 'B')}"
                          for k, v in sorted(kinds.items()))
        print(f"  {comp}: {parts}", file=out)


def _extract_reports(payload):
    """Accept an explain() report, a list of them, or what --demo
    --out-dir wrote ({"explain": {...}, "memory_ledger": {...}})."""
    if isinstance(payload, list):
        return payload
    if "explain" in payload:
        return [payload["explain"]]
    return [payload]


def run_from(path, file=None):
    with open(path) as f:
        payload = json.load(f)
    print_report_table(_extract_reports(payload), file=file)
    if isinstance(payload, dict) and payload.get("memory_ledger"):
        print_memory_summary(payload["memory_ledger"], file=file)
    return 0


def run_demo(out_dir=None):
    """Tiny GPT train program -> unbucketed storm -> explain() table."""
    import warnings

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core import framework
    from paddle_tpu.core.executor import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu.observability.compile_insight import (
        RecompileStormWarning, hbm_ledger)

    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=2, inner_size=128, max_position=64,
                        dropout=0.0)
    seq = 16
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        _tokens, loss, _logits = gpt.build_lm_net(cfg, seq_len=seq)
        fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(loss)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    rng = np.random.default_rng(0)

    def feed(b):
        return {"tokens": rng.integers(0, cfg.vocab_size, (b, seq),
                                       dtype=np.int64)}

    storms = []
    with scope_guard(scope):
        exe.run(startup)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            # 2 warm shapes, then 3 fresh ones: a storm by default
            # thresholds (warm=2, storm=3 within 60s)
            for b in (4, 8, 6, 10, 12):
                exe.run(main, feed=feed(b), fetch_list=[loss])
        storms = [w for w in caught
                  if issubclass(w.category, RecompileStormWarning)]
        report = exe.explain(main, feed=feed(4), fetch_list=[loss])

    print_report_table([report])
    print_memory_summary(hbm_ledger().snapshot())
    print(f"storm warnings: {len(storms)}"
          + (f" — {str(storms[0].message)[:140]}..." if storms else ""))
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, "compile_report_demo.json")
        with open(path, "w") as f:
            json.dump({"explain": report,
                       "memory_ledger": hbm_ledger().snapshot()}, f)
        print(f"wrote {path}")
    exe.close()
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="compile-plane report table (Executor.explain)")
    ap.add_argument("--from", dest="src", default=None,
                    help="render a saved explain() report "
                         "(what --demo --out-dir wrote)")
    ap.add_argument("--demo", action="store_true",
                    help="build a tiny GPT, storm the jit cache, "
                         "explain, print the table (CPU backend)")
    ap.add_argument("--out-dir", default=None,
                    help="--demo: also write compile_report_demo.json")
    args = ap.parse_args(argv)
    if args.demo:
        return run_demo(args.out_dir)
    if args.src:
        return run_from(args.src)
    ap.error("pass --demo or --from <json>")


if __name__ == "__main__":
    sys.exit(main())
