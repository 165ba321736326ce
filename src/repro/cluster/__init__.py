from repro.cluster.executor import ClusterExecutor, DeviceLeak, \
    DiskCheckpointer, default_trainer_factory
from repro.cluster.job import ClusterJob, JobSpec, JobState, \
    make_cluster_job
from repro.cluster.policy import Action, ScriptedPolicy, make_policy, \
    plan_actions
from repro.cluster.serving import LiveServingEngine, ServingJob, \
    ServingSpec, SyntheticServingEngine, make_serving_engine

__all__ = ["ClusterExecutor", "DeviceLeak", "DiskCheckpointer",
           "default_trainer_factory", "ClusterJob", "JobSpec", "JobState",
           "make_cluster_job", "Action", "ScriptedPolicy", "make_policy",
           "plan_actions", "ServingSpec", "ServingJob",
           "SyntheticServingEngine", "LiveServingEngine",
           "make_serving_engine"]
