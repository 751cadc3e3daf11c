"""The check of the IVF partition against the catalog's rows, on
hand-worked cases."""
import numpy as np
import pytest

from perfbench.harness import partition

ITEMS = np.array([[2.0, 0.0], [-2.0, 0.0], [0.5, 0.1], [-0.4, 0.3]], np.float32)
CENTS = np.array([[1.0, 0.0], [-1.0, 0.0]], np.float32)
# score x.c - |c|^2 / 2: item 0 -> (1.5, -2.5), 1 -> (-2.5, 1.5),
# 2 -> (0.0, -1.0), 3 -> (-0.9, -0.1)
EXACT = np.array([[0, 2, -1], [1, 3, -1]], np.int32)


def test_owners_mark_items_no_list_holds():
    lists = np.array([[0, -1], [1, 7]], np.int32)
    np.testing.assert_array_equal(partition.owners(lists, 3), [0, 1, -1])


def test_the_exact_partition_reads_zero():
    assert partition.partition_gap(ITEMS, EXACT, CENTS) == 0.0
    np.testing.assert_array_equal(partition.assign(ITEMS, CENTS, "highest"), [0, 1, 0, 1])


@pytest.mark.parametrize("lists,gap", [
    (np.array([[0, -1, -1], [1, 2, 3]], np.int32), 1.0),   # item 2 in the far list
    (np.array([[0, 2, 3], [1, -1, -1]], np.int32), 0.8),   # item 3 in the far list
], ids=["item2", "item3"])
def test_a_misplaced_item_reads_its_margin(lists, gap):
    assert partition.partition_gap(ITEMS, lists, CENTS) == pytest.approx(gap / 2.5)


def test_centroids_out_of_step_with_the_lists_read_large():
    rolled = np.roll(CENTS, 1, axis=0)
    assert partition.partition_gap(ITEMS, EXACT, rolled) == pytest.approx(4.0 / 2.5)


def test_many_blocks_agree_with_one(monkeypatch):
    rng = np.random.default_rng(3)
    items = rng.normal(size=(1000, 8)).astype(np.float32)
    cents = rng.normal(size=(16, 8)).astype(np.float32)
    owner = rng.integers(0, 16, 1000).astype(np.int32)
    whole = partition.owner_gap(items, owner, cents)
    monkeypatch.setattr(partition, "BLOCK", 128)
    partition._gap.clear_cache()
    assert partition.owner_gap(items, owner, cents) == pytest.approx(whole, rel=1e-6)
    s = items @ cents.T - 0.5 * np.sum(cents**2, axis=1)
    want = np.max(s.max(1) - s[np.arange(1000), owner]) / np.abs(s).max()
    assert whole == pytest.approx(want, rel=1e-5)
