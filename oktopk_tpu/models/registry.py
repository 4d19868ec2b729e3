"""Model registry (reference ``_support_dnns``, VGG/dl_trainer.py:39, plus
BERT). ``create_model(dnn)`` returns ``(module, example_input_fn)`` where the
example input matches the workload's dataset shapes."""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax.numpy as jnp

from oktopk_tpu.models.alexnet import AlexNet
from oktopk_tpu.models.caffe_cifar import CaffeCifar
from oktopk_tpu.models.densenet import DenseNet
from oktopk_tpu.models.laguna import Laguna, LagunaConfig
from oktopk_tpu.models.lfm2 import Lfm2, Lfm2Config
from oktopk_tpu.models.ouro import Ouro, OuroConfig
from oktopk_tpu.models.preresnet import PreResNet
from oktopk_tpu.models.qwen3_next import Qwen3Next, Qwen3NextConfig
from oktopk_tpu.models.resnext import ResNeXt
from oktopk_tpu.models.smallthinker import SmallThinker, SmallThinkerConfig
from oktopk_tpu.models.bert import BertConfig, BertForPreTraining
from oktopk_tpu.models.deepseek_v2 import DeepseekV2, DeepseekV2Config
from oktopk_tpu.models.deepspeech import DeepSpeech
from oktopk_tpu.models.imagenet_resnet import ResNet50
from oktopk_tpu.models.lstm import PTBLSTM
from oktopk_tpu.models.mnistnet import MnistNet
from oktopk_tpu.models.resnet import CifarResNet
from oktopk_tpu.models.vgg import VGG


def _img(h, w, c):
    return lambda bs: jnp.zeros((bs, h, w, c), jnp.float32)


def _tokens(t, vocab):
    return lambda bs: jnp.zeros((bs, t), jnp.int32)


# Token language models (next-token cross-entropy over ``tokens`` /
# ``targets`` [B, T]; ``apply`` returns the logits first, or, for a model
# whose class says ``computes_loss``, takes the targets too and returns its
# loss first): the sequence length and vocabulary of their data. The
# trainer's one branch for the family and the synthetic data both read this.
TOKEN_LMS: Dict[str, Tuple[int, int]] = {
    "lstm": (35, 10000),
    "lstm_tiny": (35, 1024),
    "deepseek_v2_lite": (4096, 102400),
    "deepseek_v2_tiny": (64, 512),
    "qwen3_next_80b_a3b": (8192, 151936),
    "qwen3_next_tiny": (64, 512),
    "smallthinker_21b_a3b": (16384, 151936),
    "smallthinker_tiny": (64, 512),
    "laguna_xs2": (16384, 100352),
    "laguna_tiny": (64, 512),
    "ouro_2_6b": (4096, 49152),
    "ouro_tiny": (64, 512),
    "lfm2_24b_a2b": (8192, 65536),
    "lfm2_tiny": (64, 512),
}


MODELS: Dict[str, Callable[..., Tuple[Any, Callable]]] = {
    "vgg16": lambda **kw: (VGG(name_cfg="vgg16", **kw), _img(32, 32, 3)),
    "vgg19": lambda **kw: (VGG(name_cfg="vgg19", **kw), _img(32, 32, 3)),
    "resnet20": lambda **kw: (CifarResNet(depth=20, **kw), _img(32, 32, 3)),
    "resnet56": lambda **kw: (CifarResNet(depth=56, **kw), _img(32, 32, 3)),
    "resnet110": lambda **kw: (CifarResNet(depth=110, **kw), _img(32, 32, 3)),
    "resnet50": lambda **kw: (ResNet50(**kw), _img(224, 224, 3)),
    "alexnet": lambda **kw: (AlexNet(**kw), _img(32, 32, 3)),
    "densenet100": lambda **kw: (DenseNet(**{"depth": 100, **kw}),
                                 _img(32, 32, 3)),
    "preresnet110": lambda **kw: (PreResNet(**{"depth": 110, **kw}),
                                  _img(32, 32, 3)),
    "resnext29": lambda **kw: (ResNeXt(**{"depth": 29, **kw}),
                               _img(32, 32, 3)),
    "caffe_cifar": lambda **kw: (CaffeCifar(**kw), _img(32, 32, 3)),
    "mnistnet": lambda **kw: (MnistNet(**kw), _img(28, 28, 1)),
    "lstm": lambda **kw: (PTBLSTM(**kw), _tokens(*TOKEN_LMS["lstm"])),
    # CPU-mesh-sized PTB LSTM (convergence evidence for the LSTM family,
    # the role bert_tiny plays for BERT). No dropout: the convergence probe
    # memorizes a finite pool, where the reference's keep=0.35 (applied
    # after the embedding and every layer) only drowns the algorithm
    # comparison in noise.
    "lstm_tiny": lambda **kw: (
        PTBLSTM(**{"vocab_size": 1024, "hidden_size": 192,
                   "dropout_keep": 1.0, **kw}),
        _tokens(*TOKEN_LMS["lstm_tiny"])),
    # DeepSeek-V2-Lite at its published config.json; ``held_experts`` (and,
    # for one chip's share of a job, ``num_hidden_layers``/``vocab_size``)
    # come as model_kwargs. No parameter's shape follows the sequence
    # length, so the example that initialises it is short.
    "deepseek_v2_lite": lambda **kw: (
        DeepseekV2(DeepseekV2Config(**kw)), _tokens(64, 102400)),
    "deepseek_v2_tiny": lambda **kw: (
        DeepseekV2(DeepseekV2Config.tiny(**kw)),
        _tokens(*TOKEN_LMS["deepseek_v2_tiny"])),
    # Qwen3-Next-80B-A3B-Instruct at its published config.json; a chip's
    # share comes as model_kwargs, as for deepseek_v2_lite.
    "qwen3_next_80b_a3b": lambda **kw: (
        Qwen3Next(Qwen3NextConfig(**kw)), _tokens(64, 151936)),
    "qwen3_next_tiny": lambda **kw: (
        Qwen3Next(Qwen3NextConfig.tiny(**kw)),
        _tokens(*TOKEN_LMS["qwen3_next_tiny"])),
    # SmallThinker-21BA3B-Instruct at its published config.json; a chip's
    # share comes as model_kwargs, as for deepseek_v2_lite.
    "smallthinker_21b_a3b": lambda **kw: (
        SmallThinker(SmallThinkerConfig(**kw)), _tokens(64, 151936)),
    "smallthinker_tiny": lambda **kw: (
        SmallThinker(SmallThinkerConfig.tiny(**kw)),
        _tokens(*TOKEN_LMS["smallthinker_tiny"])),
    # Laguna-XS.2 at its published config.json; a chip's share comes as
    # model_kwargs, as for deepseek_v2_lite.
    "laguna_xs2": lambda **kw: (
        Laguna(LagunaConfig(**kw)), _tokens(64, 100352)),
    "laguna_tiny": lambda **kw: (
        Laguna(LagunaConfig.tiny(**kw)),
        _tokens(*TOKEN_LMS["laguna_tiny"])),
    # Ouro-2.6B at its published config.json (one stack run total_ut_steps
    # times on the same weights); a chip's share of the depth comes as
    # model_kwargs.
    "ouro_2_6b": lambda **kw: (
        Ouro(OuroConfig(**kw)), _tokens(64, 49152)),
    "ouro_tiny": lambda **kw: (
        Ouro(OuroConfig.tiny(**kw)), _tokens(*TOKEN_LMS["ouro_tiny"])),
    # LFM2-24B-A2B at its published config.json (short-convolution and
    # attention layers, routed experts under a selection bias, a tied
    # head); a chip's share comes as model_kwargs, as for deepseek_v2_lite.
    "lfm2_24b_a2b": lambda **kw: (
        Lfm2(Lfm2Config(**kw)), _tokens(64, 65536)),
    "lfm2_tiny": lambda **kw: (
        Lfm2(Lfm2Config.tiny(**kw)), _tokens(*TOKEN_LMS["lfm2_tiny"])),
    "lstman4": lambda **kw: (DeepSpeech(**kw),
                             lambda bs: jnp.zeros((bs, 161, 201, 1),
                                                  jnp.float32)),
    # CPU-mesh-sized DeepSpeech (the CTC convergence probe, the role
    # lstm_tiny/bert_tiny play for their families): same 2-conv frontend +
    # summed-bidirectional stack, 2x128 instead of 5x800.
    "lstman4_tiny": lambda **kw: (
        DeepSpeech(**{"rnn_hidden": 128, "num_layers": 2, **kw}),
        lambda bs: jnp.zeros((bs, 161, 201, 1), jnp.float32)),
    "bert_base": lambda **kw: (
        BertForPreTraining(BertConfig.base(**kw)), _tokens(128, 30522)),
    "bert_large": lambda **kw: (
        BertForPreTraining(BertConfig.large(**kw)), _tokens(128, 30522)),
    "bert_tiny": lambda **kw: (
        BertForPreTraining(BertConfig.tiny(**kw)), _tokens(32, 1024)),
}


def create_model(dnn: str, **kw):
    try:
        factory = MODELS[dnn]
    except KeyError:
        raise ValueError(f"unknown dnn {dnn!r}; supported: {sorted(MODELS)}")
    return factory(**kw)
