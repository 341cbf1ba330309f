from repro.aformat.aggregate import AggSpec
from repro.dataset.admission import (AdmissionController, AdmissionTimeout,
                                     LANES)
from repro.dataset.dataset import Dataset, dataset
from repro.dataset.format import (AdaptiveFormat, FileFormat, ParquetFormat,
                                  PushdownParquetFormat, TaskRecord,
                                  resolve_format)
from repro.dataset.fragment import Fragment
from repro.dataset.plan import (Aggregate, Count, Filter, FragmentTask,
                                Join, JoinStrategy, Limit, PhysicalPlan,
                                PlanNode, Project, Query, Scan, ScanMetrics)
from repro.dataset.qos import (Shed, TaskContext, TenantRegistry,
                               TenantSpec, as_task_context)
from repro.dataset.scheduler import (ResultCache, ScanScheduler,
                                     modeled_latency)
from repro.dataset.snapshot import (CommitConflict, CompactionReport,
                                    Manifest, MutableDataset)

__all__ = ["AdmissionController", "AdmissionTimeout", "LANES", "AggSpec",
           "Dataset", "ScanMetrics",
           "dataset", "FileFormat", "ParquetFormat",
           "PushdownParquetFormat", "AdaptiveFormat", "TaskRecord",
           "Fragment", "ResultCache", "ScanScheduler", "modeled_latency",
           "Query", "PlanNode", "Scan", "Filter", "Project", "Aggregate",
           "Limit", "Count", "Join", "JoinStrategy", "FragmentTask",
           "PhysicalPlan",
           "resolve_format", "MutableDataset", "Manifest",
           "CommitConflict", "CompactionReport",
           "Shed", "TaskContext", "TenantRegistry", "TenantSpec",
           "as_task_context"]
