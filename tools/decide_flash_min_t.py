"""Parse a bench_flash sweep artifact and recommend PADDLE_TPU_FLASH_MIN_T.

Input: the output of tools/bench_flash.py saved to a file, lines like

    T=512   drop=0.1 pallas    1.234 ms  attn-MFU 0.345

For each (T, dropout) the kernel should engage iff it beats the XLA
path; the recommended MIN_T is the smallest T where the kernel wins at
the TRAINING configuration (dropout on) and keeps winning above.

The decision rule itself lives in the autotune harness
(``paddle_tpu.autotune.decide_threshold`` — this tool's original logic,
generalized), and ``--write-cache`` persists the recommendation into the
autotune cache so ``flash_min_t()`` consumes it as a measured decision
instead of a hand-set env default (``PADDLE_TPU_FLASH_MIN_T`` stays the
manual override; ``PADDLE_TPU_AUTOTUNE=0`` ignores the cache).

Usage:  python tools/decide_flash_min_t.py sweep.txt [--write-cache]
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def parse(path):
    rows = {}
    pat = re.compile(
        r"T=(\d+)\s+drop=([\d.]+)\s+(pallas|xla)\s+([\d.]+) ms")
    with open(path) as f:
        for line in f:
            m = pat.search(line)
            if m:
                t, drop, kind, ms = (int(m.group(1)), float(m.group(2)),
                                     m.group(3), float(m.group(4)))
                rows[(t, drop, kind)] = ms
    return rows


def main():
    # positional args exclude flags AND their value operands
    # (--backend NAME), or the backend name would be taken as the path
    args = []
    skip = False
    for a in sys.argv[1:]:
        if skip:
            skip = False
            continue
        if a == "--backend":
            skip = True
            continue
        if not a.startswith("--"):
            args.append(a)
    if not args:
        raise SystemExit(__doc__.strip().splitlines()[-1])
    path = args[0]
    rows = parse(path)
    if not rows:
        raise SystemExit("no sweep rows parsed from %s" % path)
    ts = sorted({t for t, _, _ in rows})
    drops = sorted({d for _, d, _ in rows})
    print("%-6s %-6s %10s %10s  %s" % ("T", "drop", "xla ms",
                                       "pallas ms", "winner"))
    wins = {}
    for t in ts:
        for d in drops:
            x = rows.get((t, d, "xla"))
            p = rows.get((t, d, "pallas"))
            if x is None or p is None:
                continue
            w = "pallas" if p < x else "xla"
            wins.setdefault(d, {})[t] = (w == "pallas")
            print("%-6d %-6.1f %10.3f %10.3f  %s (%.2fx)"
                  % (t, d, x, p, w, x / p))
    # recommendation keyed on the training config: the largest dropout
    # in the sweep (bench trains with attention dropout on).  The rule
    # is the autotune harness's generalized threshold decision.
    from paddle_tpu.autotune import decide_threshold

    d_train = max(drops)
    pairs = {t: (rows.get((t, d_train, "pallas")),
                 rows.get((t, d_train, "xla")))
             for t in ts
             if (t, d_train, "pallas") in rows
             and (t, d_train, "xla") in rows}
    rec = decide_threshold(pairs)
    if rec is None:
        print("\nrecommendation: kernel never cleanly wins at drop=%.1f "
              "— keep PADDLE_TPU_FLASH_MIN_T above %d (XLA path)"
              % (d_train, max(ts)))
    else:
        print("\nrecommendation: PADDLE_TPU_FLASH_MIN_T=%d "
              "(kernel wins at drop=%.1f from T=%d upward)"
              % (rec, d_train, rec))
    if "--write-cache" in sys.argv:
        from paddle_tpu.autotune import (autotune_enabled, cache_path,
                                         record_flash_min_t)

        # the sweep artifact came from a chip, but this tool often runs
        # on a workstation: the decision must be filed under the backend
        # the TRAINING process will look it up with (its device
        # platform).  --backend NAME overrides; default assumes on-chip
        # artifacts.
        backend = None
        for n, a in enumerate(sys.argv):
            if a == "--backend" and n + 1 < len(sys.argv):
                backend = sys.argv[n + 1]
        if backend is None:
            backend = "tpu"
            print("(filing the decision under backend=tpu — the sweep "
                  "artifact is an on-chip measurement; pass --backend "
                  "NAME to override)")
        if rec is None:
            print("nothing to cache (no clean win)")
        elif not autotune_enabled():
            print("PADDLE_TPU_AUTOTUNE=0 — cache write skipped")
        else:
            record_flash_min_t(rec, rows=pairs, backend=backend)
            print("cached flash_min_t=%d (backend=%s) in %s — "
                  "flash_min_t() now uses the measured decision on that "
                  "backend (env var still overrides)"
                  % (rec, backend, cache_path()))

    # block-shape decisions, if the --blocks sweep artifact exists
    # (tools/bench_flash.py --blocks; watcher step bench_flash_blocks)
    import os

    bpath = os.path.join(os.path.dirname(path) or ".",
                         "bench_flash_blocks.txt")
    try:
        with open(bpath) as f:
            decisions = [ln.strip() for ln in f
                         if ln.startswith("BLOCK-DECISION")]
    except OSError:
        decisions = []
    if decisions:
        print("\nblock-shape decisions (%s):" % bpath)
        for d in decisions:
            print("  " + d)
        print("  -> set PADDLE_TPU_FLASH_BLOCK_Q/K accordingly")


if __name__ == "__main__":
    main()
