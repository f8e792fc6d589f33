"""The route program's segmented minimum as a numpy model, no compile:
the two-level form (`routing/device.py` `_make_single`: FOLD_STEPS
doubling passes over every row, the rest over the winners of aligned
blocks of 2^FOLD_STEPS rows, the two combined at each run's last row)
against the single level of `steps` passes it replaced and against a
brute-force earliest argmin per run, over random run layouts whose
costs are drawn from 0-4, so that ties are dense.

A row's value is (cost, row): a pass keeps the earlier row on equal
costs, which is the program's tie-break (the device order is a stable
sort, so the earlier row is the lower RoutePlanes index)."""
import numpy as np
import pytest

from lightning_tpu.routing import device as RD


def _layout(rng, steps):
    """(src, cost, seg_last) of one layout: runs of equal `src` in the
    device's order, the longest of them needing exactly `steps` passes,
    runs of the lengths that sit at block edges mixed in, and padding
    rows (whose planes read node 0) behind the real ones."""
    longest = int(rng.integers((1 << (steps - 1)) + 1, (1 << steps) + 1))
    lengths = [longest]
    lengths += [n for n in (31, 32, 33, 63, 64, 65, 700, 2000)
                if n <= longest and rng.random() < 0.6]
    lengths += rng.integers(1, 9, rng.integers(5, 60)).tolist()
    lengths += rng.integers(1, longest + 1, rng.integers(0, 4)).tolist()
    rng.shuffle(lengths)
    e_real = sum(lengths)
    e_pad = 1 << max(8, (e_real - 1).bit_length() + int(rng.integers(0, 2)))
    src = np.zeros(e_pad, np.int64)
    src[:e_real] = np.repeat(np.arange(len(lengths)), lengths)
    return src, rng.integers(0, 5, e_pad), np.cumsum(lengths) - 1


def _pass(cost, row, same, shift):
    """One doubling pass: x[i] = min(x[i], x[i - shift]) where both rows
    are in one run, the earlier row kept on equal costs."""
    c_k, r_k = np.roll(cost, shift), np.roll(row, shift)
    take = same & (c_k <= cost)
    return np.where(take, c_k, cost), np.where(take, r_k, row)


def _passes(src, cost, row, n):
    at = np.arange(len(src))
    for k in range(n):
        same = (at >= 1 << k) & (src == np.roll(src, 1 << k))
        cost, row = _pass(cost, row, same, 1 << k)
    return cost, row


def _single_level(src, cost, seg_last, steps):
    c, r = _passes(src, cost, np.arange(len(src)), steps)
    return c[seg_last], r[seg_last]


def _two_level(src, cost, seg_last, steps, fold):
    block = 1 << fold
    c1, r1 = _passes(src, cost, np.arange(len(src)), min(steps, fold))
    c, r = c1[seg_last], r1[seg_last]
    if steps > fold:
        bc, br = _passes(src[block - 1::block], c1[block - 1::block],
                         r1[block - 1::block], steps - fold)
        blk_of = seg_last // block
        prev = np.maximum(blk_of - 1, 0)
        spans = (blk_of >= 1) & (
            src[np.maximum(blk_of * block - 1, 0)] == src[seg_last])
        use = spans & (bc[prev] <= c)
        c, r = np.where(use, bc[prev], c), np.where(use, br[prev], r)
    return c, r


def _brute(cost, seg_last):
    starts = np.concatenate([[0], seg_last[:-1] + 1])
    rows = [s + int(np.argmin(cost[s:e + 1]))      # the first minimum
            for s, e in zip(starts, seg_last)]
    return cost[rows], np.array(rows)


def _check(rng, steps, fold, layouts):
    for _ in range(layouts):
        src, cost, seg_last = _layout(rng, steps)
        want = _brute(cost, seg_last)
        for got in (_single_level(src, cost, seg_last, steps),
                    _two_level(src, cost, seg_last, steps, fold)):
            assert np.array_equal(got[0], want[0])
            assert np.array_equal(got[1], want[1])


@pytest.mark.parametrize("steps", range(6, 15))
def test_two_levels_at_the_programs_fold(steps):
    """At the program's own constants: the same cost and the same
    winning row at every run's last row as one level of `steps` passes
    and as the brute force, for every `steps` a hub graph has (6-14),
    the second level running `segmin_block_passes(steps)` passes."""
    assert RD._BLOCK == 1 << RD.FOLD_STEPS
    assert RD.segmin_block_passes(steps) == steps - RD.FOLD_STEPS > 0
    _check(np.random.default_rng(steps), steps, RD.FOLD_STEPS, 40)


@pytest.mark.parametrize("fold", [3, 4, 6])
def test_two_levels_at_other_folds(fold):
    """The argument does not lean on 32: folds of 8, 16 and 64 rows
    give the brute force's answers too, at every `steps` past them."""
    rng = np.random.default_rng(100 + fold)
    for steps in range(fold + 1, 15):
        _check(rng, steps, fold, 8)


def test_no_second_level_up_to_the_fold():
    """At `steps` <= FOLD_STEPS the model runs no block pass and the
    program reads none: `segmin_block_passes` is 0 there."""
    rng = np.random.default_rng(5)
    for steps in range(1, RD.FOLD_STEPS + 1):
        assert RD.segmin_block_passes(steps) == 0
        src, cost, seg_last = _layout(rng, steps)
        got = _two_level(src, cost, seg_last, steps, RD.FOLD_STEPS)
        want = _single_level(src, cost, seg_last, steps)
        assert all(np.array_equal(a, b) for a, b in zip(got, want))
