"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished, drawn
from the run's seed, is run through the plain reference once each: the
prompt followed by the served tokens, teacher-forced. At every served
position (the prefill's first token and each cached decode step) the
served logits are compared with the reference's, and each served token
with the best of its own logits.

The number compared against the reference is the root mean square of
served minus reference logits over those positions and the whole
vocabulary. It reads the precision of every position, not of the few where
two tokens nearly tie, so it is steady from seed to seed; the widest gap by
which a served token lies below the reference's best is not compared,
because it is the largest of a handful of near-ties and the float8 control
reads it at under three times a sound run's (PERF.md). A served token that
is not the best of its own logits was altered after it was produced.

The control reads the same number with the reference run in float8 in the
program's place, at the same positions of the same prompts and tokens.
"""
from __future__ import annotations

import numpy as np

from chipbench import traffic


def job_rows(seed: int, index: int, batch: int, n: int) -> list[int]:
    """Rows of window job ``index`` that the check may compare, drawn from
    the run's seed before the job runs, so that only their logits are kept."""
    rng = np.random.default_rng(traffic.stream_seed(seed, traffic.SAMPLE, index + 1))
    return sorted(int(r) for r in rng.choice(batch, size=min(n, batch), replace=False))


def sample(jobs: list, n: int, seed: int) -> list[tuple[int, int]]:
    """``n`` requests of the window, drawn from the seed, spread over as many
    jobs as they need and at least two where the window has two:
    [(job index, how many of its ``job_rows`` are compared), ...]."""
    rng = np.random.default_rng(traffic.stream_seed(seed, traffic.SAMPLE))
    k = min(len(jobs), max(2, -(-n // jobs[0].batch)))
    picked = sorted(rng.choice(len(jobs), size=k, replace=False))
    return [(int(j), min(n // k + (1 if i < n % k else 0), jobs[j].batch))
            for i, j in enumerate(picked)]


def teacher_forced(prompt: np.ndarray, tokens: np.ndarray) -> tuple[np.ndarray, int]:
    """Sequences whose logits at positions first.. predict the served tokens."""
    return np.concatenate([prompt, tokens[:, :-1]], axis=1), prompt.shape[1] - 1


def gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """best reference logit - reference logit of the chosen token, per position."""
    got = np.take_along_axis(ref_logits, chosen[..., None], axis=-1)[..., 0]
    return ref_logits.max(axis=-1) - got


def compare(reference, cfg: dict, job_seed: int, prompt: np.ndarray, tokens: np.ndarray,
            logits: np.ndarray, control: bool = False) -> tuple[float, int, int]:
    """(sum of squared logit errors, number of logits, served tokens that are
    not the best of their own logits) for some requests of one job. With
    ``control`` the float8 reference's logits stand in for the served ones."""
    seqs, first = teacher_forced(prompt, tokens)
    ref = reference.logits(cfg, job_seed, seqs, first)
    if control:
        logits = reference.logits(cfg, job_seed, seqs, first, quant="fp8")
        tokens = logits.argmax(axis=-1)
    got = np.asarray(logits, np.float32)[..., :cfg["vocab_size"]]
    err = got - ref
    altered = int((tokens != got.argmax(axis=-1)).sum())
    return float(np.square(err, dtype=np.float64).sum()), err.size, altered
