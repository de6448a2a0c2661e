"""The comparison that decides the agreement part of ``correct``: ONE
margin rule and ONE counting, whatever the architecture.  The plain
float32 forward it compares against is the cell's architecture's
(``benchmarks/architectures/<name>.py``: ``forward_top2``)."""

from __future__ import annotations


def agreement(forward_top2, params, model_config, prompts, outputs, margin: float,
              min_compared: int = 8) -> dict:
    """Teacher-forced: the reference recomputes every position of prompt +
    generated tokens; wherever its top-1 margin exceeds ``margin`` the
    engine's token has to be its argmax (the rule chip_smoke.py uses), and
    at least ``min_compared`` positions have to be decided that way."""
    import numpy as np

    seqs = [p + o for p, o in zip(prompts, outputs)]
    width = -(-max(len(s) for s in seqs) // 64) * 64
    tokens = np.zeros((len(seqs), width), np.int32)
    for r, s in enumerate(seqs):
        tokens[r, : len(s)] = s
    lens = np.asarray([len(s) for s in seqs], np.int32)
    arg, gap = forward_top2(params, model_config, tokens, lens)
    arg, gap = np.asarray(arg), np.asarray(gap)
    total = compared = equal = 0
    finite = True
    for r, (prompt, out) in enumerate(zip(prompts, outputs)):
        finite &= bool(np.isfinite(gap[r, : lens[r]]).all())
        for i, tok in enumerate(out):
            at = len(prompt) - 1 + i  # the position whose logits chose out[i]
            total += 1
            if gap[r, at] > margin:
                compared += 1
                equal += int(arg[r, at] == tok)
    return {"positions": total, "compared": compared, "equal": equal,
            "margin": margin, "min_compared": min_compared, "finite": finite,
            "ok": finite and compared >= min_compared and equal == compared}
