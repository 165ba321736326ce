"""Live reparallelization: a state-resharding subsystem (the RESHAPE verb).

EDL's original elasticity only resizes the *data* axis of a job's
``(data, model)`` mesh; this package adds the machinery to trade
data-parallel for model-parallel degree live — Tenplex-style: describe the
train state as a device-independent *parallelizable tensor collection*
(``StateSpec``), plan the minimal slice/concat/all-gather moves between any
two ``(dp, mp)`` configurations (``plan_reshard``), and execute the plan
either in memory at a mini-batch boundary (``apply_plan`` along a compiled
``StateMove``, device to device — the stop-free path every switch of
``ElasticTrainer`` commits) or through a checkpoint
(``core.stop_resume.resume_from_checkpoint`` — the fallback path that lets
a job saved at one ``(dp, mp)`` restore at another).
"""
from repro.reshape.spec import StateSpec, TensorLayout, flatten_tree, \
    unflatten_tree
from repro.reshape.plan import ReshardPlan, TensorMove, plan_reshard
from repro.reshape.apply import StateMove, apply_plan, apply_plan_host, \
    assemble_state, shard_state

__all__ = [
    "StateSpec", "TensorLayout", "flatten_tree", "unflatten_tree",
    "ReshardPlan", "TensorMove", "plan_reshard",
    "StateMove", "apply_plan", "apply_plan_host", "assemble_state",
    "shard_state",
]
