"""Deterministic synthetic batches for every workload (shapes/dtypes match
the real pipelines; used for smoke tests, benchmarks and as the zero-egress
fallback)."""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


def synthetic_batch(dnn: str, batch_size: int, rng: np.random.RandomState,
                    seq_len: int = None,
                    vocab: int = None) -> Dict[str, np.ndarray]:
    """``vocab`` overrides a token language model's vocabulary (a model
    built over a slice of it, by ``model_kwargs``)."""
    from oktopk_tpu.models.registry import TOKEN_LMS
    if dnn in TOKEN_LMS:
        t = seq_len or TOKEN_LMS[dnn][0]
        vocab = vocab or TOKEN_LMS[dnn][1]
        # Bigram-structured sequences (fixed random successor table, 10%
        # uniform noise): uniform-random tokens carry no learnable signal
        # beyond rote memorization, which makes LM loss curves useless for
        # algorithm comparisons; a bigram chain gives every optimizer the
        # same structured next-token task (entropy floor ~0.1*ln(V)), the
        # LM analogue of teacher_iterator's linear teacher for images.
        # The table comes from its own fixed-seed stream — drawing it from
        # ``rng`` would hand the infinite synthetic_iterator a fresh table
        # every batch, leaving no cross-batch signal to learn.
        trans = np.random.RandomState(vocab + 17).randint(
            0, vocab, size=(vocab,))
        toks = np.empty((batch_size, t + 1), np.int64)
        toks[:, 0] = rng.randint(0, vocab, size=(batch_size,))
        for i in range(t):
            noise = rng.rand(batch_size) < 0.1
            toks[:, i + 1] = np.where(
                noise, rng.randint(0, vocab, size=(batch_size,)),
                trans[toks[:, i]])
        return {"tokens": toks[:, :-1].astype(np.int32),
                "targets": toks[:, 1:].astype(np.int32)}
    if dnn.startswith("bert"):
        t = seq_len or (32 if dnn == "bert_tiny" else 128)
        vocab = 1024 if dnn == "bert_tiny" else 30522
        ids = rng.randint(0, vocab, size=(batch_size, t)).astype(np.int32)
        mlm = np.full((batch_size, t), -1, np.int32)
        mask_pos = rng.rand(batch_size, t) < 0.15
        mlm[mask_pos] = ids[mask_pos]
        return {"input_ids": ids,
                "token_type_ids": np.zeros((batch_size, t), np.int32),
                "attention_mask": np.ones((batch_size, t), np.int32),
                "mlm_labels": mlm,
                "nsp_labels": rng.randint(0, 2, size=(batch_size,))
                .astype(np.int32)}
    if dnn.startswith("lstman4"):
        # Tone-coded utterances: each character is rendered as ~8 frames of
        # energy in its own 5-bin frequency band (29 chars * 5 <= 161 bins)
        # over a noise floor. Random spectrograms with random labels carry
        # no audio->text relation, so CTC loss curves on them are
        # meaningless; a tone code gives the model a real alignment task —
        # the CTC analogue of the bigram chain above and the linear teacher
        # of teacher_iterator — so WER from the greedy decoder is a real
        # learning signal (reference trains DeepSpeech on AN4 to WER,
        # LSTM/dl_trainer.py:420-446, decoder VGG/decoder.py:23-197).
        f, t = 161, seq_len or 201
        fpc = 8                           # frames per character
        max_len = max(1, min(20, (t - 1) // fpc))
        min_len = min(5, max_len)         # short seq_len: fewer chars fit
        spect = (0.3 * rng.randn(batch_size, f, t, 1)).astype(np.float32)
        label_lengths = rng.randint(min_len, max_len + 1,
                                    size=(batch_size,)).astype(np.int32)
        labels = np.zeros((batch_size, 40), np.int32)
        for b in range(batch_size):
            ln = int(label_lengths[b])
            seq = rng.randint(1, 29, size=(ln,))
            labels[b, :ln] = seq
            for i, c in enumerate(seq):
                spect[b, c * 5:c * 5 + 5, i * fpc:(i + 1) * fpc, 0] += 1.0
        return {"spect": spect,
                "spect_lengths": (label_lengths * fpc).astype(np.int32),
                "labels": labels,
                "label_lengths": label_lengths}
    if dnn == "mnistnet":
        return {"image": rng.randn(batch_size, 28, 28, 1).astype(np.float32),
                "label": rng.randint(0, 10, size=(batch_size,))
                .astype(np.int32)}
    if dnn == "resnet50":
        return {"image": rng.randn(batch_size, 224, 224, 3)
                .astype(np.float32),
                "label": rng.randint(0, 1000, size=(batch_size,))
                .astype(np.int32)}
    return {"image": rng.randn(batch_size, 32, 32, 3).astype(np.float32),
            "label": rng.randint(0, 10, size=(batch_size,)).astype(np.int32)}


def synthetic_iterator(dnn: str, batch_size: int, seed: int = 0,
                       seq_len: int = None,
                       vocab: int = None) -> Iterator[Dict[str, np.ndarray]]:
    rng = np.random.RandomState(seed)
    while True:
        yield synthetic_batch(dnn, batch_size, rng, seq_len, vocab)


def finite_pool_iterator(dnn: str, batch_size: int, num_examples: int = 256,
                         seed: int = 0,
                         seq_len: int = None) -> Iterator[Dict[str, np.ndarray]]:
    """Finite synthetic dataset, shuffled and recycled forever.

    The convergence analogue of ``teacher_iterator`` for the token
    workloads (BERT/LSTM/CTC), where a linear teacher over pixels doesn't
    apply: a FINITE pool of examples is memorizable, so the loss trend is
    a real optimization signal and dense-vs-sparse gaps on the same pool
    measure the compression (fresh random tokens every step would be
    unfittable in expectation). Used by scripts/convergence.py for
    bert_*/lstm convergence evidence."""
    if batch_size > num_examples:
        raise ValueError(f"batch_size {batch_size} > pool size "
                         f"{num_examples}: the cycle would never yield")
    rng = np.random.RandomState(seed)
    pool = synthetic_batch(dnn, num_examples, rng, seq_len)
    order_rng = np.random.RandomState(seed + 1)
    while True:
        order = order_rng.permutation(num_examples)
        for i in range(0, num_examples - batch_size + 1, batch_size):
            sel = order[i:i + batch_size]
            yield {k: v[sel] for k, v in pool.items()}


def teacher_iterator(dnn: str, batch_size: int, num_examples: int = 512,
                     seed: int = 0) -> Iterator[Dict[str, np.ndarray]]:
    """Finite image dataset with *learnable* labels from a fixed random
    linear teacher (label = argmax(W @ flatten(image))).

    Random labels are unfittable in expectation, which makes loss curves
    meaningless for convergence comparisons; a teacher labeling gives every
    optimizer the same structured task, so dense-vs-sparse gaps measure the
    compression, not noise memorisation. Used by the convergence harness
    (scripts/convergence.py, tests/test_convergence.py) — the stand-in for
    the reference's accuracy-log runs (VGG/dl_trainer.py:606-616)."""
    rng = np.random.RandomState(seed)
    proto = synthetic_batch(dnn, num_examples, rng)
    if "image" not in proto:
        raise ValueError(f"teacher_iterator supports image workloads, "
                         f"not {dnn}")
    images = proto["image"]
    nclass = int(proto["label"].max()) + 1
    w = rng.randn(images[0].size, nclass).astype(np.float32)
    logits = images.reshape(num_examples, -1) @ w
    labels = np.argmax(logits, axis=1).astype(np.int32)
    order_rng = np.random.RandomState(seed + 1)
    while True:
        order = order_rng.permutation(num_examples)
        for i in range(0, num_examples - batch_size + 1, batch_size):
            sel = order[i:i + batch_size]
            yield {"image": images[sel], "label": labels[sel]}
