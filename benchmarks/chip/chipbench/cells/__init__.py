"""One driver per traffic kind (the ``kind`` key of a traffic file):
``tag_round`` for a TAG job's rounds. Each module's ``run(cell, seed,
seconds, trace, devices, t0)`` returns a ``chipbench.harness.Outcome``."""
