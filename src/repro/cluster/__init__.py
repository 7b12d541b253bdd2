"""DynMo cluster control plane: the layer between the training loop and
the job manager.

  service    — ControlPlane: off-thread profile→decide with a double-buffered
               stats mailbox and epoch-fenced plan application (§3.3.1)
  autoscaler — signal-driven shrink/grow policy (heartbeats + throughput
               watermark with hysteresis) replacing CLI-driven growth
  rpc        — JobManagerClient boundary: in-process WorkerPool wrapper and
               a file-backed stub shaped like a k8s-operator/Ray endpoint
  scheduler  — ClusterScheduler: multi-tenant arbitration (priorities,
               steal/yield, safe-point preemption) above one WorkerPool
  http_rpc   — HTTP transport for the scheduler (stdlib http.server),
               so N Sessions in N processes contend over one manager

Import the submodules directly.  The package itself imports nothing, so
the job-manager servers (``rpc``, ``http_rpc``) start without JAX.
"""
