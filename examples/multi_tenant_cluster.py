"""Multi-tenant elastic cluster demo (paper §6): three tenants share one
device pool; the scheduling policy retunes their parallelism live —
scale-in on an over-provisioned job funds scale-out (a transient loan) on a
better-scaling one, a late arrival reclaims the loan, and every device move
is a real stop-free ElasticTrainer topology switch, not a simulated tick.
Policies may also assign a running tenant 0 GPUs: the executor
checkpoint-stops it to disk, hands all of its devices to the winners, and
re-admits it from the saved state once capacity frees up.

  PYTHONPATH=src python examples/multi_tenant_cluster.py
  PYTHONPATH=src python examples/multi_tenant_cluster.py \
      --policy elastic-tiresias --devices 4
  # preemptive time-sharing under plain Tiresias
  PYTHONPATH=src python examples/multi_tenant_cluster.py \
      --policy tiresias --quanta 0.1,1000 \
      --jobs "a=resnet50:2:20@0,b=vgg19:4:12@6"
  # a model-parallel tenant (2-D data x model mesh): mp=2 makes every
  # grant/reclaim move a whole 2-device group — one data-parallel replica
  PYTHONPATH=src python examples/multi_tenant_cluster.py \
      --policy throughput \
      --jobs "big=vgg19:1:20:mp=2@0,a=resnet50:1:8@0,b=googlenet:1:6@0"
  # mp=auto leaves the degree to the scheduler: reshape-aware policies
  # may trade data- for model-parallelism live (the RESHAPE verb)
  PYTHONPATH=src python examples/multi_tenant_cluster.py \
      --policy elastic-tiresias \
      --jobs "flex=vgg19:4:20:mp=auto@0,b=googlenet:2:10@4"

Pass --jobs to change the tenant mix (grammar:
``name=profile:requested_p:total_steps[:mp=M|mp=auto]@arrival_round``;
see docs/scheduling.md for how each policy packs mixed-mp tenants and
when it reshapes mp=auto ones).
"""
import sys

# repro.launch.cluster picks the devices (on the CPU it forces a
# multi-device host platform before JAX starts), parses the job grammar,
# runs the executor, and prints the event timeline — this example is the
# human-facing entry point for it.
from repro.launch.cluster import main

if __name__ == "__main__":
    sys.exit(main())
