"""The one traffic generator: seeded training batches from a data file.

A traffic file's ``stream`` block and the configuration's ``input`` block
are all it reads. The two streams follow ``oktopk_tpu/data/synthetic.py``
at commit 669e046: ``teacher_iterator`` (images labelled by a fixed random
linear teacher, so the labels can be learnt) and the bigram chain of
``synthetic_batch`` for tokens (a fixed successor table with uniform
noise). Random labels were not copied: at lr 0.1 they drove the loss from
13 to 77 in PR 21's smoke and the selected count over two decades.

Every seed gives the same number of batches of the same shapes; only the
contents differ. All rows of all batches of one seed differ.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

Batch = Dict[str, np.ndarray]


def _images(spec, stream, global_batch: int, count: int, rng) -> List[Batch]:
    shape = tuple(spec["shape"])
    classes = int(spec["classes"])
    images = rng.standard_normal((count * global_batch,) + shape,
                                 dtype=np.float32)
    if stream.get("labels", "teacher") != "teacher":
        raise ValueError("image streams are labelled by the linear teacher")
    w = rng.standard_normal((int(np.prod(shape)), classes), dtype=np.float32)
    labels = np.argmax(images.reshape(len(images), -1) @ w,
                       axis=1).astype(np.int32)
    return [{"image": images[i * global_batch:(i + 1) * global_batch],
             "label": labels[i * global_batch:(i + 1) * global_batch]}
            for i in range(count)]


def _tokens(spec, stream, global_batch: int, count: int, rng) -> List[Batch]:
    vocab, t = int(spec["vocab"]), int(spec["seq_len"])
    noise = float(stream.get("noise", 0.1))
    # the successor table is the task, not the sample: one table per vocab
    table = np.random.default_rng(vocab + 17).integers(0, vocab, size=vocab)
    rows = count * global_batch
    toks = np.empty((rows, t + 1), np.int64)
    toks[:, 0] = rng.integers(0, vocab, size=rows)
    for i in range(t):
        noisy = rng.random(rows) < noise
        toks[:, i + 1] = np.where(noisy, rng.integers(0, vocab, size=rows),
                                  table[toks[:, i]])
    toks = toks.astype(np.int32)
    return [{"tokens": toks[i * global_batch:(i + 1) * global_batch, :-1],
             "targets": toks[i * global_batch:(i + 1) * global_batch, 1:]}
            for i in range(count)]


KINDS = {"image": _images, "tokens": _tokens}


class Feed:
    """``distinct_batches`` global batches made once from the seed, handed
    out in a seeded order for ever. ``next`` costs no generation."""

    def __init__(self, input_spec, stream, global_batch: int, seed: int):
        rng = np.random.default_rng([int(seed), 20260927])
        count = int(stream.get("distinct_batches", 4))
        self.batches = KINDS[input_spec["kind"]](
            input_spec, stream, global_batch, count, rng)
        self._order = rng.permutation(count)
        self._i = 0
        self.global_batch = global_batch

    def __iter__(self):
        return self

    def __next__(self) -> Batch:
        # the first pass is in making order, so that the first steps see
        # rows that all differ; later passes are in the seed's order
        n = len(self.batches)
        i = self._i
        self._i += 1
        return self.batches[i if i < n else self._order[i % n]]
