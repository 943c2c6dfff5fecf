"""BASELINE.md's seeded histories (``jepsen_tpu.synth.baseline_*``).

``chip_smoke.py`` and ``tools/fusioncount.py`` run these histories, so
their size, shape and exact bytes are pinned here: a change to the
generators in ``synth`` shows up as a failed digest, not as a quiet
change of workload.
"""

import functools
import hashlib

import numpy as np
import pytest

from jepsen_tpu import synth

#: name -> allowed distance of the encoded op count from the nominal
#: one: the mutex acquire-chain suffix and the 64-process register
#: rarely hit it exactly, and the scan settles for the nearest miss
_SIZE_SLACK = {"1k": 0, "mutex2k": 4, "10k": 0, "10kuniq": 0,
               "10k64": 16}

#: sha256 of each history's encoded columns (:func:`_digest`)
_GOLDEN = {
    "1k": "3a74a3a14143583e11344529aa7716c802eb53b0deb27dfbfdfb2fbf3b2cf96d",
    "mutex2k":
        "6bd6c71075dd0955285400ee13023c7a9f6f6b32b5b665fd79e36ecf0e7d0388",
    "10k": "ee2862ef97241282c88922ac16854f6e63c6fd3eab2aa3a3b7c3576f054c844f",
    "10kuniq":
        "73d9986da069d775c108e088dd82702ecd4eacc45f041aa6750e1a2fc1e25d3d",
    "10k64":
        "89f09d3c607941cc8d22f0139b4236c89bf2b611c91ba8d64ecf3ad463c3ee8e",
    # sha256 over the hex digests of batch keys 0-7, in order
    "batch0-7":
        "3e92c9e416c8a6225a458534b698fef735fd66465a7131d2a7aa1cb77aa0cfb8",
}


#: one build of each history per test process (the 10k64 scan takes
#: tens of seconds)
_history = functools.cache(synth.baseline_history)


def _digest(seq) -> str:
    h = hashlib.sha256()
    for col in ("process", "f", "v1", "v2", "inv", "ret", "ok"):
        a = np.ascontiguousarray(getattr(seq, col))
        h.update(col.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_baseline_table_names_every_history():
    assert sorted(synth.BASELINE_HISTORIES) == sorted(_SIZE_SLACK)


@pytest.mark.parametrize("name", sorted(_SIZE_SLACK))
def test_baseline_encodes_to_nominal(name):
    seq, _model = _history(name)
    nominal = synth.BASELINE_HISTORIES[name][0]
    assert abs(len(seq) - nominal) <= _SIZE_SLACK[name], len(seq)


@pytest.mark.parametrize("name", sorted(_GOLDEN))
def test_baseline_golden_digest(name):
    if name == "batch0-7":
        seqs, model = synth.baseline_batch(8)
        h = hashlib.sha256()
        for seq in seqs:
            h.update(_digest(seq).encode())
        got = h.hexdigest()
    else:
        seq, model = _history(name)
        got = _digest(seq)
    assert got == _GOLDEN[name]
    want_model = "mutex" if name == "mutex2k" else \
        "register" if name == "10kuniq" else "cas-register"
    assert model.name == want_model


def test_wide_tier_is_wide_and_near_nominal():
    # BASELINE config 5's 64-process worst-case-frontier variant: the
    # encoding must actually be wide (the history exists to stress big
    # levels)
    import jepsen_tpu.checker.linearizable as lin

    seq, _model = _history("10k64")
    es = lin.encode_search(seq)
    assert es.concurrency >= 24, es.concurrency
    assert es.window >= 128, es.window


def test_uniq_tier_exercises_value_blocks():
    """The unique-writes history is quiescence-free and eligible for
    the per-value block decomposition, which decides it with the same
    verdict as the block-order test alone."""
    from jepsen_tpu.decompose.engine import check_opseq_decomposed
    from jepsen_tpu.decompose.partition import (quiescence_segments,
                                                value_block_verdict)

    seq, model = _history("10kuniq")
    assert len(quiescence_segments(seq)) == 1  # no quiescent point
    vb = value_block_verdict(seq, model)
    assert vb in (True, False)  # the decomposition applies
    rd = check_opseq_decomposed(seq, model, sub_max_configs=1_000_000)
    assert rd["valid"] == vb
    assert "value-blocks" in (rd["decompose"].get("methods") or [])
