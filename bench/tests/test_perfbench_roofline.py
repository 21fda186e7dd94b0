"""The roofline's byte count against bytes counted by hand, and the
table of peaks."""

import pytest

import _perfbench_tiny  # noqa: F401
from bench import harness, roofline


def test_access_bytes_counted_by_hand():
    # 4-slot buckets of 28-byte slots, 5 samples in an 8-slot window,
    # 100-byte records.
    config = {"pool": {"assoc": 4, "n_samples": 5, "sample_window": 8},
              "slot_bytes": 28, "record_bytes": 100}
    counts = {"gets": 10, "sets": 6, "hits": 9, "misses": 7,
              "insert_drops": 1, "evictions": 3}
    # 16 bucket reads of 4 slots; 3 window reads of 8 slots; 3 GET hits
    # (9 hits less 6 SETs) read a record, 6 SETs and 6 inserts write one.
    want = 16 * 4 * 28 + 3 * 8 * 28 + (3 + 6 + 6) * 100
    assert roofline.access_bytes(config, counts) == want == 3964


def test_get_hits_are_never_counted_below_zero():
    config = {"pool": {"assoc": 8, "n_samples": 5}, "slot_bytes": 10,
              "record_bytes": 1000}
    counts = {"gets": 2, "sets": 6, "hits": 4, "misses": 4,
              "insert_drops": 4, "evictions": 0}
    assert roofline.access_bytes(config, counts) == 8 * 8 * 10 + 6 * 1000


def test_default_window_is_four_samples():
    config = {"pool": {"assoc": 8, "n_samples": 5}, "slot_bytes": 10,
              "record_bytes": 1000}
    counts = {"gets": 0, "sets": 0, "hits": 0, "misses": 0,
              "insert_drops": 0, "evictions": 2}
    assert roofline.access_bytes(config, counts) == 2 * 20 * 10


def test_least_seconds_at_peak_bandwidth():
    peaks = harness.peaks("TPU v5 lite")
    assert roofline.least_seconds(819e9, peaks) == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.SpecError):
        harness.peaks("cpu")
