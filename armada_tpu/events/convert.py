"""Conversions between event protos and the host-side domain dataclasses.

Plays the role of the reference's submit/conversion (api job -> SubmitJob event,
internal/server/submit/conversion/conversions.go) and the scheduler-side
adapters (internal/scheduler/adapters) in one place: our event JobSpec IS the
scheduling shape, so conversion is direct.
"""

from __future__ import annotations

from typing import Mapping, Optional

from armada_tpu.core.resources import ResourceList, ResourceListFactory
from armada_tpu.core.types import IngressSpec, JobSpec, ServiceSpec, Toleration
from armada_tpu.events import events_pb2 as pb


def resources_to_proto(rl: Optional[ResourceList]) -> pb.Resources:
    if rl is None:
        return pb.Resources()
    return pb.Resources(
        milli={name: int(a) for name, a in zip(rl.factory.names, rl.atoms) if a}
    )


def resources_from_proto(
    msg: pb.Resources, factory: ResourceListFactory
) -> ResourceList:
    rl = factory.zero()
    atoms = rl.atoms
    idx_of = factory.index_map.get
    # Key iteration + __getitem__ stay on the native map container;
    # `.items()` routes through the MutableMapping ABC machinery, which is
    # most of this function's cost on the sidecar's per-cycle 1k-submit
    # conversion path.
    milli = msg.milli
    for name in milli:
        idx = idx_of(name)
        if idx is not None:
            atoms[idx] = milli[name]
    return rl


def job_spec_to_proto(job: JobSpec) -> pb.JobSpec:
    return pb.JobSpec(
        priority_class=job.priority_class,
        priority=job.priority,
        resources=resources_to_proto(job.resources),
        node_selector=dict(job.node_selector),
        tolerations=[
            pb.Toleration(key=t.key, operator=t.operator, value=t.value, effect=t.effect)
            for t in job.tolerations
        ],
        gang_id=job.gang_id,
        gang_cardinality=job.gang_cardinality,
        gang_node_uniformity_label=job.gang_node_uniformity_label,
        pools=list(job.pools),
        price_band=job.price_band,
        namespace=job.namespace,
        annotations=dict(job.annotations),
        labels=dict(job.labels),
        services=[
            pb.ServiceSpec(type=sv.type, ports=list(sv.ports), name=sv.name)
            for sv in job.services
        ],
        ingress=[
            pb.IngressSpec(
                ports=list(ig.ports),
                annotations=dict(ig.annotations),
                tls_enabled=ig.tls_enabled,
                cert_name=ig.cert_name,
                use_cluster_ip=ig.use_cluster_ip,
            )
            for ig in job.ingress
        ],
        node_type_scores=[
            pb.NodeTypeScore(node_type=t, throughput=thr)
            for t, thr in job.node_type_scores
        ],
    )


def job_spec_from_proto(
    job_id: str,
    queue: str,
    jobset: str,
    msg: pb.JobSpec,
    factory: ResourceListFactory,
    submit_time: float = 0.0,
) -> JobSpec:
    # The collection fields are empty on the vast majority of jobs crossing
    # the sidecar boundary; len()-guarding skips the per-field container ->
    # dict/tuple conversion machinery (~a third of the conversion cost on
    # the per-cycle 1k-submit batch).
    return JobSpec(
        id=job_id,
        queue=queue,
        jobset=jobset,
        priority_class=msg.priority_class,
        priority=int(msg.priority),
        submit_time=submit_time,
        resources=resources_from_proto(msg.resources, factory),
        node_selector=dict(msg.node_selector) if len(msg.node_selector) else {},
        tolerations=tuple(
            Toleration(key=t.key, operator=t.operator or "Equal", value=t.value, effect=t.effect)
            for t in msg.tolerations
        )
        if len(msg.tolerations)
        else (),
        gang_id=msg.gang_id,
        gang_cardinality=int(msg.gang_cardinality) or 1,
        gang_node_uniformity_label=msg.gang_node_uniformity_label,
        pools=tuple(msg.pools) if len(msg.pools) else (),
        price_band=msg.price_band,
        namespace=msg.namespace or "default",
        annotations=dict(msg.annotations) if len(msg.annotations) else {},
        labels=dict(msg.labels) if len(msg.labels) else {},
        services=tuple(
            ServiceSpec(
                type=sv.type or "NodePort",
                ports=tuple(int(x) for x in sv.ports),
                name=sv.name,
            )
            for sv in msg.services
        )
        if len(msg.services)
        else (),
        ingress=tuple(
            IngressSpec(
                ports=tuple(int(x) for x in ig.ports),
                annotations=dict(ig.annotations),
                tls_enabled=ig.tls_enabled,
                cert_name=ig.cert_name,
                use_cluster_ip=ig.use_cluster_ip,
            )
            for ig in msg.ingress
        )
        if len(msg.ingress)
        else (),
        # sorted: the canonical order class_signature folds (the submit side
        # already sorts; replay from an older writer must agree)
        node_type_scores=tuple(
            sorted((x.node_type, float(x.throughput)) for x in msg.node_type_scores)
        )
        if len(msg.node_type_scores)
        else (),
    )


class SpecTemplates:
    """What `job_spec_from_proto` makes of a `spec` sub-message, interned by
    the message's bytes.

    A batch of JobStates carries a handful of distinct specs (a job set is
    thousands of jobs from one pod template, and a mirroring caller re-sends
    a job's spec with every state change), so the batch conversion
    (scheduler/sidecar.py `_jobs_from_states`) derives each ONCE and gives
    every job of it a JobSpec that SHARES the template's `resources`,
    `node_selector`, `tolerations`, `pools`, `annotations`, `labels`,
    `services`, `ingress` and `node_type_scores` objects.  Shared means
    immutable: the template's atoms are made read-only, and nothing under
    armada_tpu/ writes to a spec's mappings in place.

    Equal bytes are the same message, so a hit is exact; two encodings of one
    content (map order) are two entries, never a wrong hit.  The table is
    bounded by a plain reset: a caller whose every spec is distinct pays one
    serialisation and one store a message, and holds at most `bound` entries.
    One table per ResourceListFactory (a session owns one of each).
    """

    BOUND = 1024

    def __init__(self, factory: ResourceListFactory, bound: int = BOUND):
        self.factory = factory
        self.bound = bound
        self._fields: dict[bytes, dict] = {}
        # `lookup(bytes)` -> the template's field dict, or None: bound once
        # for the hot loop, which calls it a message (the reset in intern()
        # clears the dict in place, so the binding stays good)
        self.lookup = self._fields.get

    def __len__(self) -> int:
        return len(self._fields)

    def intern(self, key: bytes, msg: pb.JobSpec) -> dict:
        """The miss: today's one-message conversion, run once and kept.
        Returns the field dict of a JobSpec whose per-job fields are blank."""
        if len(self._fields) >= self.bound:
            self._fields.clear()
        blank = job_spec_from_proto("", "", "", msg, self.factory)
        blank.resources.atoms.flags.writeable = False
        fields = self._fields[key] = vars(blank)
        return fields
