"""Runtime services: ``fault_tolerance`` (worker pool, heartbeats,
straggler detection) and ``compression`` (gradient codecs).  Import the
submodules directly; the package imports nothing, so host-only processes
that need ``fault_tolerance`` start without JAX."""
