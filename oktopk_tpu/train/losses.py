"""Workload loss functions.

Reference criteria: CrossEntropyLoss for CNNs and PTB (VGG/dl_trainer.py:
181-186,661-677), warp-ctc CTCLoss for AN4 (:181-182 — replaced by
``optax.ctc_loss``, SURVEY.md §2.4), and BERT's masked-LM + NSP cross
entropies with ignore_index=-1 (BERT/runtime.py criterion path :573-640).
"""

from __future__ import annotations

import jax.numpy as jnp
import optax

from oktopk_tpu.collectives.state import MODEL_COUNTERS


def softmax_cross_entropy(logits, labels):
    """Mean CE over integer labels [B] (CNN classification). Logits cast
    to f32 so bf16 compute never runs the softmax reduction in bf16."""
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels).mean()


def lm_cross_entropy(logits, targets):
    """Mean CE over [B, T] targets (perplexity = exp(loss)): the loss of
    every token language model that hands back its logits, the PTB LSTM,
    ``deepseek_v2``, ``qwen3_next``, ``smallthinker`` and ``laguna``. A
    model that computes its own loss (``computes_loss``: ``models/ouro.py``,
    four exits at a head too wide for whole logits) does not come through
    here."""
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets).mean()


def model_counters(stats):
    """What a token language model says of itself beside its logits or its
    loss, as the loss function's ``{"counters": ...}`` in ``collectives/
    state.MODEL_COUNTERS``' order. A routed-expert model gives
    ``{"expert_rows": i32[expert layers, held experts]}``, the token-expert
    pairs each held expert computed; a looped model
    ``exit_step_milli_max`` under its own name. What a model does not
    report stays 0. ``{}`` for anything that is no dict (the LSTM's
    carry)."""
    if not isinstance(stats, dict):
        return {}
    found = {k: stats[k] for k in MODEL_COUNTERS if k in stats}
    if "expert_rows" in stats:
        rows = stats["expert_rows"]
        found.update(expert_rows=jnp.sum(rows),
                     expert_rows_max=jnp.max(rows, initial=0))
    return {"counters": jnp.stack([
        jnp.asarray(found.get(k, 0)).astype(jnp.int32)
        for k in MODEL_COUNTERS])}


def ctc_loss(logits, logit_lengths, labels, label_lengths, blank_id: int = 0):
    """CTC on per-frame logits [B, T, C] (replaces warpctc_pytorch).

    ``optax.ctc_loss`` wants paddings, not lengths — convert.
    """
    bt = logits.shape[:2]
    t_ids = jnp.arange(bt[1])[None, :]
    logit_pad = (t_ids >= logit_lengths[:, None]).astype(jnp.float32)
    l_ids = jnp.arange(labels.shape[1])[None, :]
    label_pad = (l_ids >= label_lengths[:, None]).astype(jnp.float32)
    per_seq = optax.ctc_loss(logits, logit_pad, labels, label_pad,
                             blank_id=blank_id)
    return per_seq.mean()


def bert_pretrain_loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels):
    """Masked-LM CE (ignore_index=-1) + next-sentence CE, as in the
    reference's pretraining criterion."""
    vocab = mlm_logits.shape[-1]
    mask = (mlm_labels >= 0).astype(jnp.float32)
    safe_labels = jnp.maximum(mlm_labels, 0)
    per_tok = optax.softmax_cross_entropy_with_integer_labels(
        mlm_logits, safe_labels)
    mlm = jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    nsp = optax.softmax_cross_entropy_with_integer_labels(
        nsp_logits, nsp_labels).mean()
    return mlm + nsp, {"mlm_loss": mlm, "nsp_loss": nsp}
