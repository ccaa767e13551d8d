"""Driver kind ``round_encoder``: the ``round`` driver's federated round
(bench/drivers/round.py: set-up, window, comparison, readings) for an
encoder with a classification head. A fresh instance of that driver, and
of the round reference (bench/reference/galore_round.py), is loaded, and
two of their pieces are swapped:

- the traffic: the stream of ``traffic/federated.py`` with ``<s>`` (id 0)
  at each row's first position, and one class id (0 to classes - 1) per
  row as its label in place of the class token at the last position;
- the plain reference: ``reference/encoder.py`` where the round reference
  reads ``reference/model.py``.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

import common
from reference import encoder as ref_encoder

BOS = 0             # RoBERTa's <s>

_round = common.load_module(common.BENCH / "drivers" / "round.py",
                            "bench_driver_round_of_encoder")
_galore = common.load_module(common.BENCH / "reference" / "galore_round.py",
                             "reference.galore_round_of_encoder")
_galore.ref_model = ref_encoder
_stream_of = _round.traffic_of


class EncoderTraffic:
    """The round stream as an encoder reads it."""

    def __init__(self, stream):
        self.stream = stream

    def round(self, index: int) -> Dict[str, np.ndarray]:
        r = self.stream.round(index)
        tokens = r["tokens"].copy()
        tokens[..., 0] = BOS
        labels = (r["labels"][..., -1] - self.stream.content).astype(np.int32)
        return {"tokens": tokens, "labels": labels}


def traffic_of(cell, conf, seed):
    return EncoderTraffic(_stream_of(cell, conf, seed))


def reference_readings(ctx, traffic, n_rounds, mode="f32"):
    arch = common.arch_config(ctx.conf)
    params = common.make_weights(arch, ctx.seed)
    rounds = [traffic.round(k) for k in range(n_rounds)]
    return _galore.reference_rounds(params, ctx.conf["arch"],
                                    _round.fed_settings(ctx.cell["fed"]),
                                    rounds, grad_round=_round.GRAD_ROUND,
                                    mode=mode)


_round.traffic_of = traffic_of
_round.reference_readings = reference_readings
run = _round.run
readings = _round.readings
device_round = _round.device_round
build_engine = _round.build_engine
