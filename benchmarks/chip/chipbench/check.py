"""Whether what the timed path served is correct.

One number with a limit of its own (``configs/<name>.json``, ``check``),
compared once the window has closed:

  logit_gap           a sample of the window's finished requests, drawn from
                      the seed and holding the longest: the plain float32
                      reference runs once over each request's row as served
                      (its left-padded prompt and its served tokens), and at
                      every served token reads how far that token's logit
                      lies below the reference's best.  The number is the
                      widest such gap.  Greedy tokens only.

The control puts the reference in the program's place at the next
precision down from the configuration's bfloat16, float8, and reads at the
same positions the gap of the token it ranks first (``control_gap``); it
is judged by the same ``verdict`` as a run.
"""
from __future__ import annotations

import numpy as np

SAMPLE_TOKENS = 1500        # served tokens the sample holds at least
SAMPLE_MAX = 12             # requests in the sample at most
NUMBERS = ("logit_gap",)


def verdict(limits: dict, got: dict) -> tuple:
    """(each number compared beside its limit, whether every one is within
    it).  A number without a limit fails."""
    checks = {n: {"value": got[n], "limit": limits[n]} for n in NUMBERS}
    return checks, all(c["limit"] is not None and c["value"] <= c["limit"]
                       for c in checks.values())


def sample(seed: int, seg, gen, done) -> list:
    """Window requests to compare: the longest finished one, then others
    in an order drawn from the seed, until the sample holds
    ``SAMPLE_TOKENS`` served tokens."""
    ids = [i for i in np.flatnonzero(done) if i in seg.generated]
    if not ids:
        return []
    longest = max(ids, key=lambda i: (gen[i], -i))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    out, tokens = [longest], int(gen[longest]) + 1
    for i in rng.permutation([i for i in ids if i != longest]):
        if tokens >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(int(i))
        tokens += int(gen[i]) + 1
    return out


def sequences(seg, reqs, length: int):
    """Reference inputs: each request's served row plus its served tokens
    but the last, right-padded to ``length``, with the positions that
    predict each served token."""
    toks, pos, served = [], [], []
    for i in reqs:
        row, out = seg.rows[i], seg.generated[i]
        seq = np.concatenate([row, out[:-1]]).astype(np.int32)
        toks.append(np.pad(seq, (0, length - len(seq))))
        pos.append(len(row) - 1 + np.arange(len(out)))
        served.append(out)
    return toks, pos, served


def reference_logits(ref, params, p, toks, pos, n_pos: int,
                     precision="float32"):
    """Per request, (n, vocab) float32 logits at ``pos``: one call per
    sequence, all of one length and ``n_pos`` positions, so one program."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda prm, t, q: ref.logits_at(prm, p, t, q, precision))
    out = []
    for t, q in zip(toks, pos):
        n = len(q)
        qp = np.zeros(n_pos, np.int32)
        qp[:n] = q
        with jax.default_matmul_precision("highest"):
            lg = fn(params, jnp.asarray(t[None]), jnp.asarray(qp[None]))
        out.append(np.asarray(lg[0, :n], np.float64))
    return out


def logit_gap(ref_logits, served) -> float:
    """Widest gap by which a served token's logit lies below the best."""
    worst = 0.0
    for lg, tok in zip(ref_logits, served):
        if np.any(tok < 0) or np.any(tok >= lg.shape[-1]):
            return float("inf")
        gap = lg.max(-1) - lg[np.arange(len(tok)), tok]
        worst = max(worst, float(gap.max()))
    return worst


def control_gap(ref_logits, control_logits) -> float:
    """Widest gap, under the float32 reference, of the token that the
    control ranks first."""
    return logit_gap(ref_logits, [c.argmax(-1) for c in control_logits])


def judge(cell, run: dict, seed: int, control: bool = False) -> dict:
    """Every number compared for one run, with the control's where asked.

    Runs once the window has closed and the memory peak is read: the
    session is closed, the engine's caches are gone, and the reference
    works on one sequence at a time."""
    import gc
    from chipbench import traffic as T
    seg, win = run["seg"], run["window"]
    done = np.isfinite(seg.t_done)
    run["session"].close()
    gc.collect()
    reqs = sample(seed, seg, win.gen, done)
    _, hi = T.output_range(cell.mix)
    toks, pos, served = sequences(seg, reqs, max(T.prompt_buckets(cell.mix))
                                  + hi)
    params = cell.engine.params
    ref = reference_logits(cell.ref, params, cell.p, toks, pos, hi)
    out = {"logit_gap": logit_gap(ref, served) if reqs else float("inf"),
           "sampled": len(reqs),
           "sampled_tokens": int(sum(len(x) for x in served)),
           "failed": int(np.sum(~done))}
    if control:
        low = reference_logits(cell.ref, params, cell.p, toks, pos, hi,
                               precision="float8")
        out["control_logit_gap"] = control_gap(ref, low)
    return out
