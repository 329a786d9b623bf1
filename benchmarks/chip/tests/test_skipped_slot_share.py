"""The reader of ``skipped_slot_share.offline``: skipped over paid forward
slots of the window's steps, with records of a program that has no
``skipped_tokens`` counter read as skipping nothing."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from registry import Registry
from repro.serving.tracing import BatchRecord

CHIP = Path(__file__).resolve().parents[1]


def test_skipped_slot_share_over_the_window():
    read = Registry({}, CHIP).metric("skipped_slot_share.offline")
    recs = [BatchRecord(step=0, ts=0.0, padded_tokens=32768,
                        skipped_tokens=14336),
            BatchRecord(step=1, ts=0.0, padded_tokens=16384)]
    assert read(SimpleNamespace(batches=recs)) == pytest.approx(
        100 * 14336 / 49152)
    # records of a program without the counter read 0, not nothing
    old = [SimpleNamespace(padded_tokens=16384, computed_tokens=14000)]
    assert read(SimpleNamespace(batches=old)) == 0.0
    assert read(SimpleNamespace(batches=[])) is None
