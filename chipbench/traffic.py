"""The one generator every traffic mix goes through.

A mix file (``chipbench/traffic/<mix>.json``) gives the shape of each batch
job: ``batch`` requests, each a ``prompt_len``-token prompt answered with
``gen`` greedy tokens, sent in a ``closed`` loop (the next job is sent when
the previous one returns). The seed of the run fixes the seed of every job,
and a job's seed fixes its prompts and its weights; the sizes never depend
on the seed, so every seed does the same work.
"""
from __future__ import annotations

import dataclasses

import numpy as np

WINDOW, WARMUP, TRACED, SAMPLE = 0, 1, 2, 3  # independent streams of one run


@dataclasses.dataclass(frozen=True)
class Job:
    seed: int
    batch: int
    prompt_len: int
    gen: int


def stream_seed(seed: int, stream: int, index: int = 0) -> int:
    """A seed below 2**31 drawn from the run's seed, whatever its size."""
    seq = np.random.SeedSequence(entropy=seed, spawn_key=(stream, index))
    return int(seq.generate_state(1)[0] >> 1)


def job(mix: dict, seed: int, stream: int, index: int = 0) -> Job:
    if mix["loop"] != "closed":
        raise ValueError(f"loop {mix['loop']!r}: only closed loops are generated")
    return Job(stream_seed(seed, stream, index), mix["batch"], mix["prompt_len"],
               mix["gen"])


def prompts(job_: Job, vocab: int) -> np.ndarray:
    """The prompts a job's seed stands for, as the serving entry states them:
    uniform token ids from numpy's default generator seeded with the job's
    seed. The harness checks the served prompts against these."""
    return np.random.default_rng(job_.seed).integers(
        0, vocab, (job_.batch, job_.prompt_len)).astype(np.int32)
