"""The writer's accounting."""

import pytest

from harness import loadgen, window


def batch(number, started, acked, checkpoints=0, **kw):
    return loadgen.Batch(number, 65536, started=started, acked=acked,
                         checkpoints=checkpoints, **kw)


def test_fixed_work_is_timed_to_its_last_acknowledgement():
    done = [batch(1, 0.1, 40.0, 1), batch(2, 40.1, 43.0, 1)]
    rates = window.ingest_rates(done, 50.0, planned=2)
    assert rates == {"ingest_rows_per_s": 2 * 65536 / 43.0,
                     "writer_cut": False}


def test_a_window_that_cuts_the_writer_divides_by_its_length():
    over = {"ingest_rows_per_s": 65536 / 50.0, "writer_cut": True}
    cut = [batch(1, 0.1, 40.0, 1), batch(2, 40.1, 53.0, 1)]
    assert window.ingest_rates(cut, 50.0, planned=2) == over
    failed = [batch(1, 0.1, 40.0, 1), batch(2, 40.1, 0.0, error="HTTP 500")]
    assert window.ingest_rates(failed, 50.0, planned=2) == over
    # the window's end fell between two batches: the last one sent was
    # acknowledged inside it, and the fixed work is still not done
    between = [batch(1, 0.1, 49.9, 1)]
    assert window.ingest_rates(between, 50.0, planned=2) == over
    assert window.ingest_rates([batch(1, 0.1, 60.0)], 50.0) == {}
    assert window.ingest_rates([], 50.0) == {}


def test_whole_cycles_are_reported_once_two_checkpoints_completed():
    many = [batch(i, i - 0.9, float(i), checkpoints=i // 4)
            for i in range(1, 13)]
    rates = window.ingest_rates(many, 50.0)
    # checkpoints were first seen after batches 4, 8 and 12
    assert rates["ingest_rows_per_s_cycles"] == pytest.approx(65536.0)
    assert rates["checkpoint_cycles"] == 2.0
    assert rates["ingest_rows_per_s"] == pytest.approx(65536.0)
    assert rates["writer_cut"] is False
