"""The one general generator of traffic. A mix is a data file under
``benchmark/traffic/``; everything drawn is a pure function of the seed,
and every seed gets the same multiset of sizes in another order."""

from __future__ import annotations

import numpy as np


def _rng(seed: int, *stream: int):
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *stream])


def train_batch(mix: dict, seed: int, step: int, rows: int, dims) -> dict:
    """Batch ``step`` of a training mix: ``rows`` rows that all differ,
    text ids in [1, vocab) (0 is the pad id) and image ids."""
    rng = _rng(seed, 1, step)
    if mix["tokens"] != "uniform":
        raise ValueError(f"unknown token law {mix['tokens']!r}")
    return {"text": rng.integers(1, dims.num_text_tokens,
                                 (rows, dims.text_seq_len), dtype=np.int32),
            "image": rng.integers(0, dims.num_image_tokens,
                                  (rows, dims.image_seq_len), dtype=np.int32)}


def prompt_lengths(mix: dict, text_seq_len: int) -> list:
    """The fixed cycle of prompt lengths of a serving mix: each of
    ``prompt_shares`` of the text window."""
    return [max(int(round(s * text_seq_len)), 1)
            for s in mix["prompt_shares"]]


def requests(mix: dict, seed: int, count: int, dims) -> list:
    """``count`` requests of a serving mix. Lengths walk the mix's fixed
    cycle in an order shuffled by the seed (each full cycle holds every
    length once); prompt ids and the sampling seed come from the seed."""
    lengths = prompt_lengths(mix, dims.text_seq_len)
    rng = _rng(seed, 2)
    out = []
    while len(out) < count:
        for n in rng.permutation(lengths):
            k = len(out)
            codes = _rng(seed, 3, k).integers(
                1, dims.num_text_tokens, int(n)).tolist()
            out.append({"codes": codes, "seed": int(rng.integers(1 << 31)),
                        "greedy": bool(mix.get("greedy", True))})
            if len(out) == count:
                break
    return out


def mean_tokens_per_request(mix: dict, dims) -> float:
    """Tokens one whole request is served: the text positions its prompt
    leaves open, then the image."""
    lengths = prompt_lengths(mix, dims.text_seq_len)
    mean_prompt = sum(lengths) / len(lengths)
    return dims.text_seq_len - mean_prompt + dims.image_seq_len


def stagger(mix: dict, slots: int, image_seq_len: int) -> list:
    """Draft lengths of the set-up wave: slot i is cut to (i + 1) / slots
    of the image, so that the whole requests which replace the drafts sit
    at phases spread over the image when the window opens."""
    return [max(int(image_seq_len * (i + 1) / slots), 1) for i in range(slots)]
