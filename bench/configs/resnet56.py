"""ResNet-56 for CIFAR-10 as the benchmark runs it.

Source: He et al., "Deep Residual Learning for Image Recognition"
(arXiv:1512.03385), section 4.2: a 3x3 stem conv of width 16, three
stages of n = 9 basic blocks at widths 16/32/64 on 32x32, 16x16 and 8x8
maps, global average pooling and a 10-way classifier; 6n + 2 = 56
layers. Built through the program's CNN (``repro.models.build`` with
``family="cnn"``, ``n_layers=27`` blocks, ``d_model=16``) at the
published widths and depth: 855,578 parameters, float32, matrix
products at JAX's ``highest`` precision (the file's ``matmul_precision``).

Two departures of the program's CNN from the source, listed in
``reduced``: a per-position norm over channels in place of batch
normalization, and a 1x1 projection (the source's option B) in place of
the zero-padded identity on the skip path of the two blocks that halve
the map.

Assumed: 128 images per worker, the source's mini-batch.

The sizes as run are in ``resnet56.json``; the plain reference, which
also makes the weights, is ``resnet56_ref.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
SIZES = json.loads((HERE / "resnet56.json").read_text())


def _ref():
    from bench.harness import load_module

    return load_module(HERE / "resnet56_ref.py")


def model_config(sizes: dict = SIZES):
    """The program's ModelConfig holding these sizes."""
    from repro.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: (tuple(v) if isinstance(v, list) else v)
          for k, v in sizes.items() if k in fields}
    return ModelConfig(**kw)


@functools.lru_cache(maxsize=None)
def _build(key: str):
    from repro.models import build as build_model

    return build_model(model_config(json.loads(key)))


def build(sizes: dict = SIZES):
    """The program's model API, through ``repro.models.build``; one object
    per size, since the runtime's step cache is keyed on it."""
    return _build(json.dumps(sizes, sort_keys=True))


def make_params(key, sizes: dict = SIZES):
    """The weights, made on the device in one jitted call from ``key``."""
    import jax

    return jax.jit(lambda k: _ref().init(k, sizes))(key)


def flops_per_sample(sizes: dict = SIZES) -> float:
    return _ref().flops_per_sample(sizes)


def reduced(sizes: dict = SIZES) -> dict:
    """The CPU tests' size: ``repro.configs.papernet.REDUCED``'s depth and
    width (one block a stage, width 8)."""
    return {**sizes, "n_layers": 2, "d_model": 8}
