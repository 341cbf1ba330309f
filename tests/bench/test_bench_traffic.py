"""The benchmark's generators: the same seed gives the same sequence of
objects, the same tables and the same batches; another seed does not."""

import itertools
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import datagen, harness  # noqa: E402
from perfbench.drivers import object_scan  # noqa: E402
from perfbench.references import mesh_ingest, predicate  # noqa: E402

SEEDS = [0, 7, 2**31 + 11, 2**40 + 3]
TAXI = harness.load_json(ROOT / "perfbench" / "configs" / "taxi_object.json")
CORPUS = harness.load_json(ROOT / "perfbench" / "configs" / "lm_corpus.json")


def objects(seed, client, n=64):
    return list(itertools.islice(object_scan.client_objects(seed, client, 8),
                                 n))


@pytest.mark.parametrize("seed", SEEDS)
def test_client_sequence_is_fixed_by_the_seed(seed):
    assert objects(seed, 0) == objects(seed, 0)
    assert objects(seed, 0) != objects(seed + 1, 0)
    assert objects(seed, 0) != objects(seed, 1)
    assert set(objects(seed, 0, 400)) == set(range(8))


@pytest.mark.parametrize("seed", SEEDS)
def test_object_tables_are_fixed_by_the_seed(seed):
    cfg = dict(TAXI, rows_per_object=5000)
    a = object_scan.object_columns(cfg, seed, 3)
    b = object_scan.object_columns(cfg, seed, 3)
    c = object_scan.object_columns(cfg, seed + 1, 3)
    assert list(a) == [s["name"] for s in TAXI["columns"]]
    for k in a:
        assert list(a[k]) == list(b[k])
    assert a["trip_distance"].tobytes() != c["trip_distance"].tobytes()
    assert a["trip_distance"].tobytes() != object_scan.object_columns(
        cfg, seed, 4)["trip_distance"].tobytes()


def test_taxi_columns_keep_the_published_types_and_resolution():
    """The TLC's field types, amounts to the cent, times to the second,
    and a total that is the sum of its parts."""
    cols = object_scan.object_columns(dict(TAXI, rows_per_object=20000),
                                      2**31 + 1, 0)
    types = {s["name"]: s["type"] for s in TAXI["columns"]}
    assert len(types) == 19
    for name, v in cols.items():
        want = object if types[name] == "string" else np.dtype(types[name])
        assert v.dtype == want, name
    for name in ("trip_distance", "fare_amount", "tip_amount",
                 "tolls_amount", "total_amount"):
        assert np.array_equal(cols[name], np.round(cols[name], 2)), name
    assert len(np.unique(cols["fare_amount"])) < 10_000
    pick, drop = cols["tpep_pickup_datetime"], cols["tpep_dropoff_datetime"]
    assert np.all(pick % 1_000_000 == 0) and np.all(drop % 1_000_000 == 0)
    assert np.all(drop >= pick)
    parts = ["fare_amount", "extra", "mta_tax", "tip_amount",
             "tolls_amount", "improvement_surcharge",
             "congestion_surcharge", "airport_fee"]
    assert np.allclose(cols["total_amount"],
                       sum(cols[p] for p in parts), atol=0.005)
    assert set(cols["store_and_fwd_flag"]) == {"N", "Y"}
    width = sum(8 for t in types.values() if t != "string") + 4 + 1
    assert TAXI["row_bytes"] == width
    assert TAXI["rows_per_object"] == (128 << 20) // width


def test_every_seed_makes_the_same_sizes():
    """Seeds change values and order, never the amount of work."""
    cfg = dict(CORPUS, row_group_rows=3000)
    for seed in SEEDS:
        shard = datagen.corpus_shard(cfg, seed, 2)
        assert {len(v) for v in shard.values()} == {6000}
        lens = np.diff(np.flatnonzero(np.r_[True, np.diff(
            shard["doc_id"]) != 0, True]))
        assert lens[:-1].min() >= 16
        assert np.all(shard["pos"][np.r_[0, np.cumsum(lens)[:-1]]] == 0)


@pytest.mark.parametrize("seed", SEEDS)
def test_corpus_and_its_batches_are_fixed_by_the_seed(seed):
    cfg = dict(CORPUS, row_group_rows=3000, seq_len=31, local_batch=2)
    terms = predicate.resolve({"and": [{"column": "quality", "op": ">",
                                        "value": 0.5}]}, None)

    def first_batches(s):
        shards = [datagen.corpus_shard(cfg, s, i) for i in range(4)]
        streams = mesh_ingest.streams(
            shards, row_group_rows=3000, terms=terms, positions=4, seed=s,
            local_batch=2, seq_len=31)
        return [st.batch(k)["tokens"] for st in streams for k in range(40)]

    a, b, c = first_batches(seed), first_batches(seed), first_batches(
        seed + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))


def test_split_is_largest_first_onto_the_lightest():
    assert mesh_ingest.split([5, 5, 5, 5], 2) == [[0, 2], [1, 3]]
    assert mesh_ingest.split([1, 9, 4, 4], 2) == [[1], [0, 2, 3]]
    assert mesh_ingest.split([3], 2) == [[0], []]


def test_draws_are_typed_and_shares_kept():
    rng = datagen.rng_for(5, 0)
    v = datagen.draw(rng, {"draw": "constant", "value": 2.5,
                           "type": "float32", "nonzero_share": 0.3}, 20000)
    assert v.dtype == np.float32 and set(np.unique(v)) == {0.0, 2.5}
    assert 0.27 < np.mean(v > 0) < 0.33
    r = datagen.draw(rng, {"draw": "gamma", "shape": 2.0, "scale": 600.0,
                           "round": 0, "times": 1000, "type": "int64"}, 50)
    assert r.dtype == np.int64 and np.all(r % 1000 == 0)
    with pytest.raises(ValueError):
        datagen.draw(rng, {"draw": "zipf"}, 3)
