"""The plain per-lane reference that decides ``correct``.

A frozen copy of the simulator's per-access semantics (the stage
composition, caches, page-table walkers and predictor counters), run
one (system, workload) lane at a time on the system's own static
config: its own table sizes and stage list, with no system vmap, no
padding, no mesh, no trace stacking, and none of the ladder batching
(no allocation at the ladder's largest sizes, no per-lane sizing
scalars, no stage gates).  It imports nothing of the program under
test, so a later change to the program's scan or dispatch is held
against the semantics as they stood when the benchmark was defined.

``lane.py`` holds the driver (step, finalize, extras); the modules
beside it are copies of ``repro.core``'s with their imports pointed
here and the ladder-batching paths taken out.  One change besides:
``stages.base.zero_stats`` and ``make_state`` take the dtype of the
cycle sums, so the control can run the same lanes with bfloat16 sums.
"""
