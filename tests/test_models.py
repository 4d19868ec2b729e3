"""Model zoo shape/param tests (the reference has none — SURVEY.md §4)."""

import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oktopk_tpu.models import create_model, registry
from oktopk_tpu.models.bert import BertConfig, BertForPreTraining
from oktopk_tpu.models.deepspeech import DeepSpeech
from oktopk_tpu.models.lstm import PTBLSTM


def nparams(params):
    return sum(x.size for x in jax.tree.leaves(params))


# the decoder models, one file a model, and what they are built from: the
# arrows point one way (ops/ <- shared parts <- a model <- registry.py)
DECODERS = ("deepseek_v2", "qwen3_next", "smallthinker", "laguna", "ouro",
            "lfm2")
SHARED = ("attention", "moe", "layers")


def imported_from_models(name):
    """The modules of ``oktopk_tpu.models`` that ``models/<name>.py``
    imports, anywhere in the file."""
    path = pathlib.Path(registry.__file__).parent / f"{name}.py"
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, f"{name}: a relative import"
            names = ([f"{node.module}.{a.name}" for a in node.names]
                     if node.module == "oktopk_tpu.models"
                     else [node.module])
        else:
            continue
        for n in names:
            if n.startswith("oktopk_tpu.models"):
                found.add(n.split(".")[2] if n.count(".") > 1 else "")
    return found


@pytest.mark.parametrize("name", DECODERS + SHARED)
def test_no_decoder_model_is_built_out_of_another(name):
    """A model file imports the three shared modules only; a shared module
    imports no model file (nor the package, whose ``__init__`` imports them
    all through the registry)."""
    found = imported_from_models(name)
    assert "attention" in found or name in SHARED   # the parse finds them
    assert found <= set(SHARED) - {name}, found


class TestConvNets:
    @pytest.mark.parametrize("dnn,classes", [
        ("vgg16", 10), ("resnet20", 10), ("alexnet", 10), ("mnistnet", 10)])
    def test_forward_shape(self, dnn, classes):
        model, example = create_model(dnn)
        x = example(2)
        variables = model.init(jax.random.PRNGKey(0), x, train=False)
        y = model.apply(variables, x, train=False)
        assert y.shape == (2, classes)
        assert np.all(np.isfinite(np.asarray(y)))

    def test_vgg16_param_count(self):
        # torch VGG16+BN CIFAR head is ~15.0M params; ours must match the
        # architecture scale (reference VGG/models/vgg.py cfg D)
        model, example = create_model("vgg16")
        v = model.init(jax.random.PRNGKey(0), example(1), train=False)
        n = nparams(v["params"])
        assert 14e6 < n < 16e6, n

    def test_batchnorm_state_updates(self):
        model, example = create_model("resnet20")
        x = jnp.asarray(np.random.RandomState(0)
                        .randn(4, 32, 32, 3).astype(np.float32))
        variables = model.init(jax.random.PRNGKey(0), x, train=False)
        _, mutated = model.apply(variables, x, train=True,
                                 mutable=["batch_stats"])
        old = jax.tree.leaves(variables["batch_stats"])
        new = jax.tree.leaves(mutated["batch_stats"])
        assert any(not np.allclose(a, b) for a, b in zip(old, new))


class TestSequenceModels:
    def test_ptb_lstm_carry(self):
        model = PTBLSTM(vocab_size=50, hidden_size=16, num_layers=2)
        toks = jnp.zeros((2, 7), jnp.int32)
        v = model.init(jax.random.PRNGKey(0), toks, train=False)
        logits, carry = model.apply(v, toks, train=False)
        assert logits.shape == (2, 7, 50)
        assert len(carry) == 2
        # carry feeds back in
        logits2, _ = model.apply(v, toks, carry=carry, train=False)
        assert logits2.shape == (2, 7, 50)

    def test_deepspeech_frames(self):
        model = DeepSpeech(num_classes=29, rnn_hidden=32, num_layers=2)
        x = jnp.zeros((1, 161, 41, 1), jnp.float32)
        v = model.init(jax.random.PRNGKey(0), x, train=False)
        y = model.apply(v, x, train=False)
        # time downsampled only by conv1's stride 2 (conv2 stride (2,1))
        assert y.shape[0] == 1 and y.shape[2] == 29
        assert y.shape[1] == 21


class TestBert:
    def test_pretraining_heads(self):
        cfg = BertConfig.tiny()
        model = BertForPreTraining(cfg)
        ids = jnp.zeros((2, 16), jnp.int32)
        v = model.init(jax.random.PRNGKey(0), ids, ids,
                       jnp.ones_like(ids), train=False)
        mlm, nsp = model.apply(v, ids, ids, jnp.ones_like(ids), train=False)
        assert mlm.shape == (2, 16, cfg.vocab_size)
        assert nsp.shape == (2, 2)

    def test_weight_tying(self):
        """MLM decoder must react to the embedding table (tied weights,
        reference depth=4/__init__.py:17)."""
        cfg = BertConfig.tiny()
        model = BertForPreTraining(cfg)
        ids = jnp.zeros((1, 8), jnp.int32)
        v = model.init(jax.random.PRNGKey(0), ids, ids,
                       jnp.ones_like(ids), train=False)
        mlm1, _ = model.apply(v, ids, ids, jnp.ones_like(ids), train=False)
        v2 = jax.tree_util.tree_map(lambda x: x, v)
        emb = v2["params"]["bert"]["embeddings"]["word_embeddings"]["embedding"]
        v2["params"]["bert"]["embeddings"]["word_embeddings"]["embedding"] = \
            emb * 2.0
        mlm2, _ = model.apply(v2, ids, ids, jnp.ones_like(ids), train=False)
        assert not np.allclose(np.asarray(mlm1), np.asarray(mlm2))

    def test_attention_mask_respected(self):
        cfg = BertConfig.tiny()
        model = BertForPreTraining(cfg)
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 8)), jnp.int32)
        v = model.init(jax.random.PRNGKey(0), ids, jnp.zeros_like(ids),
                       jnp.ones_like(ids), train=False)
        mask = jnp.asarray([[1, 1, 1, 1, 0, 0, 0, 0]], jnp.int32)
        out1, _ = model.apply(v, ids, jnp.zeros_like(ids), mask, train=False)
        # changing masked-out tokens must not change unmasked positions
        ids2 = ids.at[0, 6].set((int(ids[0, 6]) + 1) % cfg.vocab_size)
        out2, _ = model.apply(v, ids2, jnp.zeros_like(ids), mask, train=False)
        np.testing.assert_allclose(np.asarray(out1[0, :4]),
                                   np.asarray(out2[0, :4]), atol=1e-5)
