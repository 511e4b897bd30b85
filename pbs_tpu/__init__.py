"""PBS-T: a TPU-native performance-feedback scheduling framework.

Re-expresses the capability set of the reference ``5l1v3r1/PBS`` (a Xen
4.2.1 + Linux 3.2.30 research stack: Perfctr-xen virtualized hardware
performance counters + a PMU-feedback adaptive time-slice credit scheduler)
idiomatically for TPUs with JAX/XLA/Pallas/pjit:

- ``pbs_tpu.telemetry``  — per-job virtualized telemetry ledgers with
  lock-free seqlock snapshot reads (analog of perfctr's shared counter
  pages, ``linux-3.2.30/drivers/perfctr/x86.c:228-312``).
- ``pbs_tpu.runtime``    — jobs (domain/vCPU analogs), executors
  (the ``schedule()`` softirq loop, ``xen/common/schedule.c:1082-1185``),
  partitions (cpupools), event channels, job images (pygrub analog),
  lifecycle hooks (hotplug scripts), compile-cache admission.
- ``pbs_tpu.sched``      — pluggable scheduler framework + policies:
  credit (``xen/common/sched_credit.c``), credit2, sedf, arinc653, and
  the PMU-feedback adaptive quantum policy (the research core).
- ``pbs_tpu.parallel``   — device-mesh partitions, dp/tp/pp/sp/ep
  shardings, ring attention / sequence parallelism, gang scheduling.
- ``pbs_tpu.ops``        — Pallas TPU kernels (instrumented matmul,
  blockwise flash/ring attention).
- ``pbs_tpu.models``     — flagship workloads (decoder transformer, MoE).
- ``pbs_tpu.ckpt``       — checkpoint/resume; with ``pbs_tpu.dist``,
  Remus-style continuous replication to a backup host
  (``tools/libxc/xc_domain_save.c``, ``tools/remus``).
- ``pbs_tpu.obs``        — trace rings, software perf counters, monitors,
  per-job consoles, hot-path perf canaries (``xen/common/trace.c``,
  ``tools/xenmon``, ``tools/xenstat``, ``drivers/perfctr/x86_tests.c``).
- ``pbs_tpu.store``      — hierarchical config/rendezvous store
  (xenstore analog).
- ``pbs_tpu.cli``        — ``pbst`` management CLI (``xl`` analog).
"""

import time as _time

#: ``time.monotonic_ns()`` at the package's first import: what
#: ``HOST_START`` falls back on where the process's own start cannot be
#: read (``obs.trace.process_start_ns``).
T_IMPORT_NS = _time.monotonic_ns()

__version__ = "0.1.0"
