"""Hypothesis property tests on the data substrate invariants."""

import numpy as np
import pytest
pytest.importorskip("hypothesis")  # offline containers: skip, do not error
from hypothesis import given, settings, strategies as st

from repro.data import (
    ActivationStore,
    class_histogram,
    dirichlet_partition,
    federate,
    heterogeneity_index,
    load_store,
    make_lm_dataset,
    make_vision_dataset,
    round_batches,
)


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(50, 400),
    k=st.integers(2, 12),
    alpha=st.floats(0.05, 1.0),
    classes=st.integers(2, 10),
    seed=st.integers(0, 2**31 - 1),
)
def test_dirichlet_partition_is_a_partition(n, k, alpha, classes, seed):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, classes, n)
    parts = dirichlet_partition(labels, k, alpha, rng)
    allidx = np.concatenate(parts)
    # exact partition: every index exactly once
    assert sorted(allidx.tolist()) == list(range(n))
    # every client non-empty
    assert all(len(p) >= 1 for p in parts)


def test_alpha_controls_heterogeneity():
    """Smaller alpha -> more heterogeneous label distributions (paper Fig 4
    premise).  Checked in expectation over several seeds."""
    labels = np.random.default_rng(0).integers(0, 10, 4000)
    het = {}
    for alpha in (0.1, 1.0):
        vals = []
        for seed in range(5):
            rng = np.random.default_rng(seed)
            parts = dirichlet_partition(labels, 10, alpha, rng)
            h = class_histogram(labels, parts, 10)
            vals.append(heterogeneity_index(h))
        het[alpha] = np.mean(vals)
    assert het[0.1] > het[1.0] + 0.1


@settings(max_examples=10, deadline=None)
@given(bs=st.integers(1, 33), steps=st.integers(1, 5))
def test_round_batches_shapes(bs, steps):
    ds = make_vision_dataset(64, seed=0)
    clients = federate(ds, 4, 0.5, seed=0)
    batches = round_batches(clients, [0, 2, 1], steps, bs)
    assert batches["images"].shape[:3] == (3, steps, bs)
    assert batches["labels"].shape == (3, steps, bs)


def test_client_batches_cycle_without_repeat_within_epoch():
    ds = make_vision_dataset(40, seed=0)
    clients = federate(ds, 2, 1.0, seed=0)
    c = clients[0]
    n = len(c)
    got = c.batches(n, 1)["labels"][0]
    assert len(got) == n


# ---------------------------------------------------------------------------
# activation store
# ---------------------------------------------------------------------------


def test_store_consolidation_pools_all_clients():
    st_ = ActivationStore(consolidated=True, seed=0)
    for cid in range(3):
        st_.add(cid, {"acts": np.full((10, 4), cid, np.float32),
                      "labels": np.full((10,), cid, np.int32)})
    assert st_.num_samples() == 30
    seen = set()
    for b in st_.batches(10, epochs=1):
        seen.update(np.unique(b["labels"]).tolist())
    assert seen == {0, 1, 2}  # batches mix clients


def test_store_per_client_mode():
    st_ = ActivationStore(consolidated=False, seed=0)
    for cid in range(2):
        st_.add(cid, {"acts": np.full((8, 4), cid, np.float32),
                      "labels": np.full((8,), cid, np.int32)})
    for cid in range(2):
        for b in st_.batches(4, epochs=1, client_id=cid):
            assert (b["labels"] == cid).all()


@settings(max_examples=10, deadline=None)
@given(scale=st.floats(0.01, 100.0), seed=st.integers(0, 1000))
def test_store_int8_quantization_roundtrip(scale, seed):
    rng = np.random.default_rng(seed)
    acts = (rng.normal(0, scale, (16, 32))).astype(np.float32)
    st_ = ActivationStore(consolidated=True, quantize_int8=True, seed=0)
    st_.add(0, {"acts": acts, "labels": np.arange(16, dtype=np.int32)})
    batch = next(iter(st_.batches(16)))
    # batches are shuffled — restore row order via the label key
    order = np.argsort(batch["labels"])
    got = batch["acts"][order]
    # per-row absmax int8: error bounded by scale/2 per row (+ float slack)
    row_absmax = np.abs(acts).max(axis=1, keepdims=True)
    bound = row_absmax / 127.0 * 0.5 + row_absmax * 1e-6 + 1e-7
    assert (np.abs(got - acts) <= bound).all()


def test_store_quantization_shrinks_bytes():
    acts = np.random.default_rng(0).normal(0, 1, (64, 128)).astype(np.float32)
    a = ActivationStore(consolidated=True, quantize_int8=False)
    b = ActivationStore(consolidated=True, quantize_int8=True)
    a.add(0, {"acts": acts, "labels": np.zeros(64, np.int32)})
    b.add(0, {"acts": acts, "labels": np.zeros(64, np.int32)})
    assert b.bytes_received < 0.35 * a.bytes_received


def test_store_disk_roundtrip(tmp_path):
    d = str(tmp_path / "acts")
    st_ = ActivationStore(directory=d, consolidated=True, seed=0)
    st_.add(3, {"acts": np.arange(12, dtype=np.float32).reshape(3, 4),
                "labels": np.asarray([1, 2, 3], np.int32)})
    st2 = load_store(d)
    assert st2.num_samples() == 3
    b = next(iter(st2.batches(3)))
    assert set(b["labels"].tolist()) == {1, 2, 3}


def test_store_async_writer_and_streaming():
    st_ = ActivationStore(consolidated=True, seed=0)
    st_.start_writer()
    for cid in range(4):
        st_.submit(cid, {"acts": np.ones((8, 4), np.float32) * cid,
                         "labels": np.full((8,), cid, np.int32)})
    st_.finish()
    n = 0
    for b in st_.streaming_batches(8):
        n += 1
        if n > 64:
            break
    assert st_.num_samples() == 32
    assert n >= 4


def _uneven_store(quantize=False, consolidated=True, seed=11, sizes=None):
    """A seeded store of uneven shards, single-row ones among them,
    spread over three clients in interleaved order."""
    st_ = ActivationStore(consolidated=consolidated, quantize_int8=quantize,
                          seed=seed)
    r = np.random.default_rng(5)
    sizes = sizes or [7, 1, 13, 1, 1, 30, 2, 9, 1, 17, 5, 1, 24]
    for i, n in enumerate(sizes):
        st_.add(i % 3, {"acts": r.normal(0, 3, (n, 5, 6)).astype(np.float32),
                        "labels": r.integers(0, 10, n).astype(np.int32)})
    return st_


def _pool_epoch(pool, rng, batch_size, dequantize):
    """One epoch drawn as the store once drew it: one permutation of the
    concatenated pool, batches indexed from it, the remainder dropped."""
    n = len(pool["acts"])
    order = rng.permutation(n)
    for s in range(0, n - batch_size + 1, batch_size):
        b = {k: v[order[s:s + batch_size]] for k, v in pool.items()}
        if dequantize and "acts_scale" in b:
            b["acts"] = b["acts"].astype(np.float32) * b.pop("acts_scale")
        yield b


def _assert_same_batches(got, want):
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert g.keys() == w.keys()
        for k in g:
            assert g[k].dtype == w[k].dtype and g[k].shape == w[k].shape
            np.testing.assert_array_equal(g[k], w[k])


@pytest.mark.parametrize("quantize,dequantize,client_id,epochs", [
    (False, True, None, 3),         # float32, consolidated
    (True, True, None, 2),          # int8 + acts_scale, dequantized
    (True, False, None, 2),         # int8 + acts_scale, as stored
    (False, True, 1, 2),            # per-client mode
    (True, False, 2, 2),            # per-client, int8 as stored
], ids=["f32", "int8-dequant", "int8-raw", "per-client", "per-client-int8"])
def test_store_batches_match_concatenated_pool(quantize, dequantize,
                                               client_id, epochs):
    """Batches gathered from the shards are bit-identical, in order,
    membership, dtype and bytes, to the same seed's batches from the
    concatenated pool."""
    consolidated = client_id is None
    st_ = _uneven_store(quantize, consolidated)
    pool = _uneven_store(quantize, consolidated).pool(client_id)
    rng = np.random.default_rng(11)
    for bs in (4, 9):
        got = list(st_.batches(bs, epochs=epochs, client_id=client_id,
                               dequantize=dequantize))
        want = [b for _ in range(epochs)
                for b in _pool_epoch(pool, rng, bs, dequantize)]
        _assert_same_batches(got, want)


def test_streaming_batches_gather_late_shards_bit_identical():
    """``streaming_batches`` draws an epoch over each snapshot, then one
    over the complete pool after ``finish()``, late single-row shards
    included, each as the concatenated snapshot would give it."""
    st_ = _uneven_store(sizes=[7, 1, 13])
    gen = st_.streaming_batches(4)
    early = [next(gen) for _ in range(5)]            # 21 rows: one epoch
    st_.add(1, {"acts": np.full((1, 5, 6), 7, np.float32),
                "labels": np.full((1,), 77, np.int32)})
    st_.add(2, {"acts": np.full((3, 5, 6), 8, np.float32),
                "labels": np.full((3,), 88, np.int32)})
    st_.finish()
    rest = list(gen)
    rng = np.random.default_rng(11)
    first = _uneven_store(sizes=[7, 1, 13]).pool()
    _assert_same_batches(early, list(_pool_epoch(first, rng, 4, True)))
    _assert_same_batches(rest, list(_pool_epoch(st_.pool(), rng, 4, True)))
    lab = np.concatenate([b["labels"] for b in rest])
    assert (lab == 77).sum() + (lab == 88).sum() >= 3


@pytest.mark.parametrize("draw", ["batches", "streaming_batches"])
def test_streamed_epoch_never_builds_the_pool(draw, monkeypatch):
    st_ = _uneven_store()
    st_.finish()

    def no_pool(*a, **k):
        raise AssertionError("a streamed epoch concatenated the pool")

    monkeypatch.setattr(st_, "_pool", no_pool)
    got = list(getattr(st_, draw)(8))
    assert len(got) == st_.num_samples() // 8
    assert st_.pool_concat_bytes == 0


def test_pool_concat_bytes_counts_whole_pools():
    st_ = _uneven_store(quantize=True)
    assert st_.pool_concat_bytes == 0
    st_.pool()
    assert st_.pool_concat_bytes == st_.pool_nbytes()
    st_.pool(dequantize=True)
    assert st_.pool_concat_bytes == 2 * st_.pool_nbytes()


def test_lm_dataset_domain_structure():
    ds = make_lm_dataset(64, seq_len=32, vocab=53, num_domains=4, seed=0)
    assert ds.arrays["tokens"].shape == (64, 32)
    assert ds.arrays["tokens"].max() < 53
    assert set(np.unique(ds.labels)) <= set(range(4))
