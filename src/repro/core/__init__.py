"""The paper's contribution: demand-driven auto-scaling provisioning of
Kubernetes-managed resources into HTCondor pools (Sfiligoi et al., PEARC22).
"""
from repro.core.classad import ClassAdExpr, symmetric_match, UNDEFINED
from repro.core.events import EventHandle, EventLoop, PeriodicHandle
from repro.core.fairshare import (
    Accountant, ScheddSpec, UsageLedger, job_cores, make_schedd_specs,
)
from repro.core.jobqueue import (
    FlockedQueues, Job, JobQueue, JobState, cohort_key_of, user_of,
)
from repro.core.cluster import KubeCluster, Node, Pod, PodPhase
from repro.core.matchmaker import (
    JaxMatchmaker, MatchPlan, MatchProblem, Matchmaker,
    NumpyMatchmaker, RESOURCE_KEYS, ScanMatchmaker, make_matchmaker,
    matchmaker_names, register_matchmaker,
)
from repro.core.worker import (
    Collector, LRUCache, Worker, advance_workers, kill_worker,
)
from repro.core.groups import GroupSignature, group_jobs, signature_of
from repro.core.config import (
    BackendConfig, FairshareConfig, ProvisionerConfig, dump_ini, load_ini,
    PAPER_EXAMPLE_INI,
)
from repro.core.backend import (
    FederatedClusterView, KubeBackend, PodSpec, ROUTING_POLICIES,
    RoutingPolicy, ScalingBackend, adapt_single_cluster, backend_from_config,
    build_backends, make_routing_policy,
)
from repro.core.provisioner import Provisioner
from repro.core.nodescaler import NodeAutoscaler, NodeTemplate
from repro.core.simulation import Simulation, gpu_job, onprem_nodes
from repro.core.metrics import (
    CompletedStats, Recorder, percentile, summarize_backends, timeline,
)
from repro.core.stragglers import StragglerPolicy
