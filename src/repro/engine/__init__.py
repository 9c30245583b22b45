"""repro.engine — the unified SNN execution-plan API.

One :class:`SNNEnginePlan` (frozen) + one :class:`SNNEngine` (three
verbs) replace the ~10 scattered SNN entrypoints that each re-accepted
``threshold``/``leak``/``ltp_prob``/``backend``/``t_chunk``/``mesh``
kwargs.  The engine owns kernel-path dispatch (``ref``/``interp``/
``tpu``) and neuron-mesh placement; consumers (``repro.core.trainer``,
``repro.serving.snn``) drive its verbs.

Migration table (old call -> plan verb)
---------------------------------------

===========================================================  ==========================================================
old call                                                     engine equivalent
===========================================================  ==========================================================
``snn_mesh.sharded_infer_window_batch(..., mesh=m)``         ``SNNEngine(replace(plan, mesh=m)).infer(w, wins)``
``snn_mesh.sharded_train_window_batch(..., mesh=m2d)``       ``SNNEngine(replace(plan, mesh_shape=(d, n))).train_batch``
``trainer kwargs (kernel_backend/window_chunk/...)``         ``SNNEnginePlan`` fields / ``plan_from_config(cfg)``
===========================================================  ==========================================================

where ``plan = SNNEnginePlan(threshold=..., leak=..., w_exp=...,
gain=..., n_syn=..., ltp_prob=..., kernel_backend=..., t_chunk=...)``
is built once (or via :func:`plan_from_config` from an
``SNNTrainConfig``), and ``replace`` is ``dataclasses.replace``.
Placement is an explicit ``mesh`` or the declarative
``mesh_shape=(data, neurons)`` — the 2-D grid shards batch axes over
"data" and regfiles over "neurons"; the verbs dispatch 1-D vs 2-D
automatically and every factorization is bit-exact with the unsharded
path.
"""

from repro.engine.engine import (SNNEngine, SNNOutput, refresh_weights,
                                 reset_between_samples, train_stream,
                                 train_stream_batch)
from repro.engine.plan import SNNEnginePlan, plan_from_config

__all__ = ["SNNEngine", "SNNEnginePlan", "SNNOutput", "plan_from_config",
           "refresh_weights", "reset_between_samples", "train_stream",
           "train_stream_batch"]
