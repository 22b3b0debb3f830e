"""How a run reaches the program's normal path without the event store.

Two small subclasses and the factories that wire them to the stock
preparator, algorithm and serving classes; nothing else of the program's
logic lives in the benchmark. ``pio import`` managed 6.9k events a second on
the chip host (PERF.md, PR 21), so no run can ingest its ratings: the data
source hands the configuration's seeded ratings over in memory, and the serve
cells' algorithm returns seeded factor tables where the stock one would fit.
Named as ``engineFactory`` in the ``engine.json`` a run writes.
"""

from __future__ import annotations

import numpy as np

from benchmarks import seeded
from predictionio_tpu.controller.base import DataSource
from predictionio_tpu.controller.engine import Engine
from predictionio_tpu.controller.serving import FirstServing
from predictionio_tpu.models.recommendation.engine import (
    ALSAlgorithm, RatingsData, RecommendationModel, RecommendationPreparator)
from predictionio_tpu.parallel.als import ALSModel

class SeededRatingsSource(DataSource):
    """Params: ``users``, ``items``, ``ratings`` (counts) and ``seed``."""

    def read_training(self, ctx):
        p = self.params
        n_users, n_items = p["users"], p["items"]  # a Params is a dict
        # a token set, drawn uniformly: the seeded algorithm ignores it
        rng = seeded.rng_for(p["seed"], 0)
        users = rng.integers(0, n_users, size=p["ratings"])
        items = rng.integers(0, n_items, size=p["ratings"])
        ratings = rng.integers(1, 6, size=p["ratings"]).astype(np.float32)
        return RatingsData(
            users=users, items=items, ratings=ratings,
            times=np.zeros(users.size, dtype=np.float64),
            user_ids=[seeded.user_id(n) for n in range(n_users)],
            item_ids=[seeded.item_id(n) for n in range(n_items)],
            app_name="benchmark", event_names=["rate"],
        )


class SeededFactorsALS(ALSAlgorithm):
    """``train`` returns the model a fit would, with factors drawn from the
    seed (params ``seed``): float32, N(0, 1/sqrt(rank)), nothing seen."""

    def train(self, ctx, prepared) -> RecommendationModel:
        ratings_data, _ = prepared
        rank = self.params.get_or("rank", 16)
        seed = self.params.seed
        als = ALSModel(
            user_factors=seeded.make_factors(
                ratings_data.num_users, rank, seed, seeded.USER_STREAM),
            item_factors=seeded.make_factors(
                ratings_data.num_items, rank, seed, seeded.ITEM_STREAM),
        )
        return RecommendationModel(
            als=als,
            user_index={u: n for n, u in enumerate(ratings_data.user_ids)},
            item_ids=ratings_data.item_ids,
            item_index={i: n for n, i in enumerate(ratings_data.item_ids)},
            seen={}, seen_mode="model",
        )


def seeded_factors_engine() -> Engine:
    return Engine(
        data_source_class=SeededRatingsSource,
        preparator_class=RecommendationPreparator,
        algorithm_class_map={"als": SeededFactorsALS},
        serving_class=FirstServing,
    )
