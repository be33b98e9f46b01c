"""The benchmark's plain reference: a FROZEN copy of the program's serial
scheduler and of the object model it evaluates.

Copied at PR 26 from ``kubernetes_tpu/oracle/{state,filters,scores,
pipeline}.py``, ``kubernetes_tpu/api/{labels,resource,types}.py`` and
``kubernetes_tpu/util/nodetree.py`` with only the import lines rewritten to
relative ones.  Nothing here imports the program, so a later PR cannot move
the yardstick by editing the program's oracle or its types: the reference
is handed plain specs (``benchmarks/workload.py``) and node names, builds
its OWN ``Node``/``Pod`` objects from them, and answers one pod at a time
(``pipeline.schedule_one``: every filter over every node, the default
score weights, deterministic first-max tie-break in node order).
"""
