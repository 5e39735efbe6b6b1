"""Fleet-scale serving: region simulation over sharded engine replicas.

The millions-of-users story on top of the unified serving engine —
everything before this package measured a handful of closed-loop
sessions on one host; here the load is open-loop and the serving plane
is a fleet:

  * ``workload`` — seeded Poisson / diurnal-modulated arrival processes
    spawning whole incident sessions (``IncidentSession``) at a
    configurable offered rate, with stochastic intra-session modality
    lags carried as explicit per-event arrival sequences through
    ``core.episodes.async_episode(times=...)``.
  * ``region`` — ``RegionSim``: N ``EMSServeEngine`` replicas built
    from ONE ``build_engine`` spec, replica r's parameters on device
    ``r % n_devices``, a consistent-hash + least-loaded session router,
    and a shared simulated clock (flush cost = measured wall seconds of the
    real XLA calls, flush start gated on data availability).
  * ``admission`` — deadline/queue-depth admission control with
    hysteresis; overload sheds NEW sessions to the on-glass provisional
    path (``GlassShedPath``) where they receive ``degraded``-tagged
    partials instead of queueing the backlog to death.

Benchmark: ``benchmarks/fleet_load.py`` (latency-vs-offered-load knee,
sessions/s scaling vs replica count, shed-vs-queue A/B) ->
``BENCH_fleet.json``. Launcher: ``python -m repro.launch.serve
--fleet RATE --replicas N``.
"""
from .admission import AdmissionController, AdmissionPolicy, AdmitAll  # noqa: F401
from .region import (ConsistentHashRouter, DegradedRecord,  # noqa: F401
                     GlassShedPath, RegionSim, place_replica_params)
from .workload import (IncidentSession, diurnal_rate,  # noqa: F401
                       diurnal_times, generate_workload, merge_sessions,
                       poisson_times)
