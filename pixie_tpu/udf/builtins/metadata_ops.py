"""Metadata scalar UDFs: k8s entity lookups against the MetadataState.

Ref: src/carnot/funcs/metadata/metadata_ops.* (UPIDToServiceNameUDF et al.,
resolved against AgentMetadataState via FunctionContext). All host-executed
and dict_compatible: UPIDs/IPs are dictionary-encoded strings, so each
distinct process/endpoint resolves once per query, not once per row.
"""

from __future__ import annotations

import numpy as np

from pixie_tpu.types import DataType, SemanticType
from pixie_tpu.udf.registry import Registry
from pixie_tpu.udf.udf import Executor, ScalarUDF

S = DataType.STRING
I = DataType.INT64


def _lift(fn, out_dtype=object):
    def wrapper(ctx, *cols):
        state = ctx.metadata_state
        n = max((len(c) for c in cols if isinstance(c, np.ndarray)), default=1)
        out = np.empty(n, dtype=out_dtype)
        for i in range(n):
            args = [c[i] if isinstance(c, np.ndarray) else c for c in cols]
            out[i] = fn(state, *args)
        return out

    return wrapper


def register(r: Registry) -> None:
    def reg(name, args, out, fn, out_dtype=object, semantic=None):
        r.register_scalar(
            ScalarUDF(
                name,
                args,
                out,
                _lift(fn, out_dtype),
                Executor.HOST,
                dict_compatible=True,
                needs_ctx=True,
                out_semantic=semantic,
            )
        )

    # -- UPID resolvers ----------------------------------------------------
    def pod_of(st, upid):
        return st.pod_for_upid(upid)

    reg(
        "upid_to_pod_id",
        (S,),
        S,
        lambda st, u: (pod_of(st, u).pod_id if pod_of(st, u) else ""),
    )
    reg(
        "upid_to_pod_name",
        (S,),
        S,
        lambda st, u: (pod_of(st, u).name if pod_of(st, u) else ""),
        semantic=SemanticType.ST_POD_NAME,
    )
    reg(
        "upid_to_namespace",
        (S,),
        S,
        lambda st, u: (pod_of(st, u).namespace if pod_of(st, u) else ""),
        semantic=SemanticType.ST_NAMESPACE_NAME,
    )
    reg(
        "upid_to_node_name",
        (S,),
        S,
        lambda st, u: (pod_of(st, u).node_name if pod_of(st, u) else ""),
        semantic=SemanticType.ST_NODE_NAME,
    )

    def svc_of(st, upid):
        return st.service_for_upid(upid)

    reg(
        "upid_to_service_name",
        (S,),
        S,
        lambda st, u: (svc_of(st, u).name if svc_of(st, u) else ""),
        semantic=SemanticType.ST_SERVICE_NAME,
    )
    reg(
        "upid_to_service_id",
        (S,),
        S,
        lambda st, u: (svc_of(st, u).service_id if svc_of(st, u) else ""),
    )

    def upid_to_pid(st, u):
        try:
            return int(u.split(":")[1])
        except (IndexError, ValueError):
            return -1

    reg("upid_to_pid", (S,), I, upid_to_pid, np.int64)

    def upid_to_asid(st, u):
        try:
            return int(u.split(":")[0])
        except (IndexError, ValueError):
            return -1

    reg("upid_to_asid", (S,), I, upid_to_asid, np.int64)

    # -- pod/service id resolvers -----------------------------------------
    reg(
        "pod_id_to_pod_name",
        (S,),
        S,
        lambda st, pid: st.pods[pid].name if pid in st.pods else "",
        semantic=SemanticType.ST_POD_NAME,
    )
    reg(
        "pod_id_to_service_name",
        (S,),
        S,
        lambda st, pid: (
            st.services[st.pods[pid].service_id].name
            if pid in st.pods and st.pods[pid].service_id in st.services
            else ""
        ),
        semantic=SemanticType.ST_SERVICE_NAME,
    )
    reg(
        "pod_id_to_service_id",
        (S,),
        S,
        lambda st, pid: st.pods[pid].service_id if pid in st.pods else "",
    )
    reg(
        "pod_id_to_namespace",
        (S,),
        S,
        lambda st, pid: st.pods[pid].namespace if pid in st.pods else "",
        semantic=SemanticType.ST_NAMESPACE_NAME,
    )
    reg(
        "service_id_to_service_name",
        (S,),
        S,
        lambda st, sid: st.services[sid].name if sid in st.services else "",
        semantic=SemanticType.ST_SERVICE_NAME,
    )
    reg(
        "ip_to_pod_id",
        (S,),
        S,
        lambda st, ip: st.pod_for_ip(ip).pod_id if st.pod_for_ip(ip) else "",
    )

    def _ip_to_service_id(st, ip):
        pod = st.pod_for_ip(ip)
        return pod.service_id if pod is not None else ""

    reg("ip_to_service_id", (S,), S, _ip_to_service_id)

    def _pod_id_to_node_name(st, pid):
        pod = st.pods.get(pid)
        return pod.node_name if pod is not None else ""

    reg(
        "pod_id_to_node_name",
        (S,),
        S,
        _pod_id_to_node_name,
        semantic=SemanticType.ST_NODE_NAME,
    )
    reg(
        "nslookup",
        (S,),
        S,
        # With no metadata state, every address is unresolved: it comes
        # back as itself, as an address the state cannot resolve does.
        lambda st, ip: ip if st is None else st.dns.get(ip, ip),
    )
    reg("_exec_hostname", (), S, lambda st: st.hostname)

    def _num_cpus(st):
        import os

        return os.cpu_count() or 1

    r.register_scalar(
        ScalarUDF(
            "_exec_host_num_cpus",
            (),
            I,
            _lift(lambda st: _num_cpus(st), np.int64),
            Executor.HOST,
            dict_compatible=False,
            needs_ctx=True,
        )
    )
    reg(
        "upid_to_container_name",
        (S,),
        S,
        lambda st, u: st.upid_to_container.get(u, ""),
    )
    reg(
        "upid_to_container_id",
        (S,),
        S,
        # Container ids are container names prefixed per-pod in the
        # synthetic state (no containerd runtime here); resolves to ""
        # when unknown, like the reference on missing metadata.
        lambda st, u: st.upid_to_container.get(u, ""),
    )
    reg(
        "upid_to_cmdline",
        (S,),
        S,
        lambda st, u: st.upid_to_cmdline.get(u, ""),
    )

    def _has_name(st, col_val, want):
        # Ref: HasServiceNameUDF (metadata_ops.h:3096): equality OR
        # membership when the column holds a JSON array of names (pods
        # backing several services).
        if col_val == want:
            return True
        if col_val.startswith("["):
            try:
                import json

                return want in json.loads(col_val)
            except ValueError:
                return False
        return False

    reg("has_service_name", (S, S), DataType.BOOLEAN, _has_name, np.bool_)
    reg("has_service_id", (S, S), DataType.BOOLEAN, _has_name, np.bool_)
    reg(
        "container_id_to_status",
        (S,),
        S,
        # Ref: ContainerIDToStatusUDF (metadata_ops.h:2859) — JSON status
        # blob; without a container runtime the state/reason mirror the
        # pod-status shape for known containers.
        lambda st, cid: (
            '{"state":"Running","message":"","reason":""}'
            if cid
            else '{"state":"Unknown","message":"","reason":""}'
        ),
    )
    reg("pod_name_to_pod_id", (S,), S,
        lambda st, name: next(
            (p.pod_id for p in st.pods.values() if p.name == name), ""
        ))

    def _pod_by_name(st, name):
        return next((p for p in st.pods.values() if p.name == name), None)

    reg(
        "pod_name_to_start_time",
        (S,),
        DataType.TIME64NS,
        lambda st, name: (
            _pod_by_name(st, name).start_time_ns
            if _pod_by_name(st, name)
            else 0
        ),
    )
    reg(
        "pod_name_to_status",
        (S,),
        S,
        lambda st, name: (
            '{"phase":"%s","message":"","reason":"","ready":true}'
            % _pod_by_name(st, name).phase
            if _pod_by_name(st, name)
            else '{"phase":"Unknown","message":"","reason":"","ready":false}'
        ),
    )
    reg(
        "pod_name_to_pod_ip",
        (S,),
        S,
        lambda st, name: (
            _pod_by_name(st, name).ip if _pod_by_name(st, name) else ""
        ),
        semantic=SemanticType.ST_IP_ADDRESS,
    )
    reg("service_name_to_service_id", (S,), S,
        lambda st, name: next(
            (s.service_id for s in st.services.values() if s.name == name), ""
        ))
