"""``seq_attention_tile_share``: the reader of the counts the span ``seq.pack``
carries since PR 31 (``attention_tiles``, ``attention_tiles_worked``)."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

NAME = "seq_attention_tile_share"


def _reader():
    from run import load_module

    return load_module("layer_metrics", NAME)


def _pack(lengths, max_len):
    from predictionio_tpu.controller import Params
    from predictionio_tpu.models.sequence.engine import SequencePreparator, SequencesData

    data = SequencesData([np.arange(n, dtype=np.int64) % 50 for n in lengths],
                         [str(u) for u in range(len(lengths))],
                         [str(i) for i in range(50)])
    return SequencePreparator(Params({"maxLen": max_len})).prepare(None, data)


def test_the_entry_is_the_kernels_layer_and_this_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower", "source": "program_span",
        "layer": "flash attention kernel", "moves": "train_iters_per_s",
        "workloads": ["ouro-2.6b-d8.train-histories"]}


def test_it_reads_the_share_of_tiles_the_preparator_counted():
    """Rows of 256 at block 128: a history of at most 128 events works one
    tile of four, a longer one three (the one above the diagonal is left)."""
    _pack([20, 100, 128, 129, 300], 256)
    assert _reader().read({}) == pytest.approx(100.0 * (1 + 1 + 1 + 3 + 3) / 20)
    # one block a row (the template's default maxLen): every tile is worked
    _pack([5, 64, 70], 64)
    assert _reader().read({}) == 100.0


def test_a_span_without_the_counts_gives_nothing():
    """The parent's ``seq.pack`` carries ``slots`` and ``filled_slots`` only."""
    from predictionio_tpu.obs.trace import global_tracer

    with global_tracer().span("seq.pack") as span:
        span.set_attr("slots", 512)
        span.set_attr("filled_slots", 100)
    assert _reader().read({}) is None
