"""Fleet tier: routed multi-worker checking service.

One front router process (``router.py``) spreads run namespaces across
N ``stream.service`` workers by rendezvous hashing, probes worker
health on ``reconnect.Backoff`` schedules, and re-routes a dead
worker's runs after salvaging their persisted verdicts.  Workers share
one verdict-cache store through per-worker write-ahead segments
(``cachestore.py``), warm-boot their steady-state kernels before
admission (``warmup.py``), and an admission controller turns shed
rate / open runs / fold backlog into accept / shed / spawn-worker
decisions (``admission.py``).

``python -m jepsen_tpu.fleet`` wires the pieces into a running tier.
See docs/fleet.md for the walkthrough.
"""

from .admission import AdmissionController, AdmissionPolicy  # noqa: F401
from .cachestore import FleetCacheStore  # noqa: F401
from .router import (  # noqa: F401
    FleetRouter,
    WorkerSpec,
    route_run,
)
