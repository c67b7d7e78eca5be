import numpy as np

from reflow_tpu.delta import DeltaBatch, Spec, collection_counter


def test_empty():
    b = DeltaBatch.empty()
    assert len(b) == 0
    assert len(DeltaBatch.concat([b, b])) == 0


def test_from_pairs_and_consolidate():
    b = DeltaBatch.from_pairs([("a", 1), ("b", 2), ("a", 1)])
    assert len(b) == 3
    c = b.consolidate()
    assert c.to_counter() == {("a", 1): 2, ("b", 2): 1}


def test_retraction_cancels():
    ins = DeltaBatch.from_pairs([("a", 1)])
    ret = DeltaBatch.from_pairs([("a", 1)], weight=-1)
    assert DeltaBatch.concat([ins, ret]).consolidate().to_counter() == {}


def test_numeric_columns():
    b = DeltaBatch(np.array([3, 1, 3]), np.array([1.0, 2.0, 3.0]),
                   np.array([1, 1, -1]))
    acc = collection_counter([b])
    assert acc == {(3, 1.0): 1, (1, 2.0): 1, (3, 3.0): -1}


def test_spec():
    s = Spec((768,), np.float32).with_key_space(1000)
    assert s.key_space == 1000
    e = DeltaBatch.empty(s)
    assert e.values.shape == (0, 768)


def test_padded_appends_weight_zero_rows_in_the_batch_value_form():
    b = DeltaBatch(np.array([3, 1, 4], np.int64),
                   np.arange(6, dtype=np.float32).reshape(3, 2),
                   np.array([1, -1, 2], np.int64))
    p = b.padded(5)
    assert len(p) == 5
    assert np.array_equal(p.keys, [3, 1, 4, 0, 0])
    assert np.array_equal(p.weights, [1, -1, 2, 0, 0])
    assert p.values.shape == (5, 2) and p.values.dtype == np.float32
    assert np.array_equal(p.values[:3], b.values)
    assert not p.values[3:].any()
    # weight-0 rows are semantic no-ops
    assert p.to_counter() == b.to_counter()
    # at or past the row count the batch comes back as it is
    assert b.padded(3) is b and b.padded(2) is b
