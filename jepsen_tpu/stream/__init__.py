"""Streaming incremental checking — live verdicts while the test runs.

Everything else in this repo checks post-hoc: the runner finishes, then
the checker replays the whole history.  This subsystem turns the
quiescence-cut machinery (``decompose/partition.py``: segments compose
sequentially through reachable-state sets, P-compositionality,
arXiv:1504.00204) into an *online* checker:

  * :mod:`checker` — :class:`StreamChecker`, the op sink: incremental
    event pairing, online per-cell quiescence-cut detection, immediate
    folding of closed segments against the carried-forward
    reachable-state frontier (canonical-hash verdict cache first), and
    a live provisional verdict (``valid-so-far`` / final ``invalid`` /
    ``open``) the whole way.  ``finalize()`` emits a proof-carrying
    result identical to the post-hoc engines.
  * :mod:`device` — wide segment folds dispatched to the batched
    device engine (checker/bucket.py) via state-pinning pseudo-ops,
    the GPUexplore split (arXiv:1801.05857): accelerated search on
    device, cheap sequential composition on host.
  * :mod:`service` / ``python -m jepsen_tpu.stream`` — a long-running
    service multiplexing history JSONL from many concurrent runs over
    stdin or a socket, all sharing one verdict cache: the fleet only
    ever pays for novel segments.

Wiring: ``core.prepare_test`` installs the sink next to the
StreamLinter behind ``JEPSEN_TPU_STREAM=1`` / CLI ``--stream``;
``core.run`` finalizes it on success AND on worker-abort paths (a
crashed run still yields the verdict of the prefix it recorded);
``web.py`` serves the live snapshot at ``/api/live/<run>`` and renders
the live panel; the streaming-applicability gate lives in
``analyze.plan.stream_plan`` / ``segment_fold_route`` so prediction
and execution cannot drift.
"""

from .checker import StreamChecker, stream_enabled
from .service import StreamService

__all__ = ["StreamChecker", "StreamService", "stream_enabled"]
