"""NEURON-Fabric core: low-bit gradient aggregation with admission control.

The paper's contribution as a composable JAX module:

  * :mod:`modes`        — aggregation modes + payload accounting (Table 2)
  * :mod:`lowbit`       — G-Binary / G-Ternary / FP32 collectives (Section 2/3)
  * :mod:`buckets`      — param groups, admission plans (Section 7.3)
  * :mod:`aggregate`    — error-feedback state for tree-level aggregation
  * :mod:`admission`    — Predictor / Commander / Supervisor (Section 3/8)
  * :mod:`diagnostics`  — cosine-alignment layer diagnostics (Table 5)
  * :mod:`traffic`      — payload ratios + wire-byte/time models (Table 6, Fig 7)
  * :mod:`exposure`     — datapath timing-exposure model (Section 5, Fig 3)
"""
from .modes import (AggregationMode, Schedule, bits_per_element,
                    canonical_mode, codec_name, schedule_name,
                    traffic_ratio, wire_schedule)
from .lowbit import (LeafPolicy, fp32_allreduce, lowbit_packed_a2a,
                     lowbit_vote_psum, majority_sign_sgd, sign_of_mean)
from .buckets import (AdmissionPlan, Bucket, BucketGate, BucketKey,
                      BucketLayout, BucketSlot, DEFAULT_BUCKET_BYTES,
                      GroupPolicy, GroupRules, UnfusedLeaf, assign_groups,
                      group_sizes, path_name, plan_buckets,
                      resolve_policies)
from .aggregate import init_ef_states, make_policy_tree
from .admission import Commander, CusumGuard, Predictor, Supervisor
from .diagnostics import (cosines_to_host, group_cosines_from_mean,
                          group_cosines_from_workers)
from .traffic import (IciModel, MultiHopModel, hop_wire_bytes_per_device,
                      modeled_comm_time, modeled_layout_comm_time,
                      modeled_layout_multihop_time, payload_bytes,
                      plan_traffic_ratio, wire_bytes_per_device)
from .exposure import ExposureModel, TpuDatapathModel, envelope_sweep

__all__ = [
    "AggregationMode", "Schedule", "bits_per_element", "canonical_mode",
    "codec_name", "schedule_name", "traffic_ratio", "wire_schedule",
    "LeafPolicy", "fp32_allreduce", "lowbit_packed_a2a",
    "lowbit_vote_psum", "majority_sign_sgd", "sign_of_mean",
    "AdmissionPlan", "Bucket", "BucketGate", "BucketKey", "BucketLayout",
    "BucketSlot", "DEFAULT_BUCKET_BYTES", "GroupPolicy", "GroupRules",
    "UnfusedLeaf", "assign_groups", "group_sizes", "path_name",
    "plan_buckets", "resolve_policies",
    "init_ef_states", "make_policy_tree",
    "Commander", "CusumGuard", "Predictor", "Supervisor",
    "cosines_to_host", "group_cosines_from_mean", "group_cosines_from_workers",
    "IciModel", "MultiHopModel", "hop_wire_bytes_per_device",
    "modeled_comm_time", "modeled_layout_comm_time",
    "modeled_layout_multihop_time", "payload_bytes", "plan_traffic_ratio",
    "wire_bytes_per_device",
    "ExposureModel", "TpuDatapathModel", "envelope_sweep",
]
