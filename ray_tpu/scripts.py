"""`ray-tpu` CLI: start/stop/status/submit/logs against a local cluster.

Re-design of the reference's CLI (reference: python/ray/scripts/scripts.py:626
`ray start` / `ray stop` / `ray status`; job commands from
dashboard/modules/job/cli.py). The head's session directory is the
address; `start` records it at ~/.ray_tpu/latest_session so later
commands find the cluster without arguments.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_SESSION_POINTER = os.path.expanduser("~/.ray_tpu/latest_session")


def _record_session(session_dir: str) -> None:
    os.makedirs(os.path.dirname(_SESSION_POINTER), exist_ok=True)
    with open(_SESSION_POINTER, "w") as f:
        f.write(session_dir)


def _detach_cluster(cluster) -> None:
    """Detaches a Cluster's daemons from this CLI process so they outlive
    it (reference: `ray start` leaving raylets running): drop the
    kill-children atexit hook, record every daemon pid for `stop`, and
    point the latest-session file here."""
    import atexit

    atexit.unregister(cluster.shutdown)
    pids = [p.pid for p in cluster._procs]
    with open(os.path.join(cluster.session_dir, "pids.json"), "w") as f:
        json.dump(pids, f)
    _record_session(cluster.session_dir)


def _resolve_address(args) -> str:
    if getattr(args, "address", None):
        return args.address
    try:
        with open(_SESSION_POINTER) as f:
            return f.read().strip()
    except OSError:
        raise SystemExit("no running cluster found; pass --address or run `ray-tpu start`")


def cmd_start(args) -> None:
    from .core.cluster_runtime import Cluster, start_worker_node

    resources = json.loads(args.resources) if args.resources else None
    labels = json.loads(args.labels) if getattr(args, "labels", None) else None
    if args.address:
        # Worker-node mode (reference: `ray start --address=head:port`).
        info = start_worker_node(
            args.address,
            node_ip=args.node_ip_address,
            num_cpus=args.num_cpus,
            num_tpus=args.num_tpus,
            resources=resources,
            object_store_memory=args.object_store_memory,
            labels=labels,
        )
        with open(os.path.join(info["session_dir"], "pids.json"), "w") as f:
            json.dump([info["proc"].pid], f)
        # Per-host stop semantics (like `ray stop`): `ray-tpu stop` on this
        # host finds and kills this raylet.
        _record_session(info["session_dir"])
        print(
            f"joined cluster at {args.address}; node {info['node_id']} "
            f"(session dir: {info['session_dir']})"
        )
        return
    node_ip = args.node_ip_address
    if node_ip is None:
        # With a TCP port the whole point is reachability from OTHER
        # hosts: default to this machine's primary routable ip (the UDP
        # "connect" trick needs no egress), not loopback — a printed
        # tcp://127.0.0.1 join address would point every joiner at itself.
        node_ip = "127.0.0.1"
        if args.port is not None:
            import socket as _socket

            probe = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
            try:
                probe.connect(("10.255.255.255", 1))
                node_ip = probe.getsockname()[0]
            except OSError:
                pass
            finally:
                probe.close()
    cluster = Cluster(
        num_cpus=args.num_cpus,
        num_tpus=args.num_tpus,
        resources=resources,
        object_store_memory=args.object_store_memory,
        head_port=args.port,
        node_ip=node_ip,
        labels=labels,
    )
    _detach_cluster(cluster)
    print(f"started cluster; session dir: {cluster.session_dir}")
    print(f"connect with: ray_tpu.init(address={cluster.session_dir!r})")
    if cluster.gcs_tcp_address:
        print(
            f"other hosts join with: ray-tpu start --address {cluster.gcs_tcp_address}"
        )


def cmd_stop(args) -> None:
    session = _resolve_address(args)
    try:
        with open(os.path.join(session, "pids.json")) as f:
            pids = json.load(f)
    except OSError:
        pids = []
    from .core import proctree
    from .core.zygote import PidHandle

    proctree.end_session(session, [PidHandle(pid) for pid in pids])
    # Reclaim tmpfs pools + session state: nothing else unlinks them once
    # the CLI detached the cluster from the atexit cleanup.
    import glob
    import shutil

    for store in glob.glob(f"/dev/shm/rtpu_{os.path.basename(session)}_*"):
        try:
            os.unlink(store)
        except OSError:
            pass
    shutil.rmtree(session, ignore_errors=True)
    try:
        os.unlink(_SESSION_POINTER)
    except OSError:
        pass
    print(f"stopped {len(pids)} cluster processes")


def _connect(args):
    from . import api

    api.init(address=_resolve_address(args), ignore_reinit_error=True)


_STATUS_AUTO_SUMMARY = 64  # per-node rows above this need an explicit ask


def cmd_status(args) -> None:
    _connect(args)
    from .utils import state

    stats = state.cluster_stats()
    print(f"nodes alive: {stats['nodes_alive']}")
    # At scale, the per-node dump is the enemy: ONE summary RPC (O(1)
    # reply) + an optional bounded node sample replaces pulling and
    # printing a megabyte table for 1000 nodes.
    summary = state.node_summary()
    limit = getattr(args, "limit", None)
    if getattr(args, "summary", False) or (
        limit is None and summary["total"] > _STATUS_AUTO_SUMMARY
    ):
        print(
            f"nodes: {summary['total']} total "
            + " ".join(f"{k}={v}" for k, v in sorted(summary["by_state"].items()))
        )
        print(f"  resources: {summary['resources']}")
        print(f"  available: {summary['available']}")
        if not getattr(args, "summary", False):
            print(
                f"  (per-node rows suppressed at >{_STATUS_AUTO_SUMMARY} "
                f"nodes; use --limit N for a sample)"
            )
        _status_tail(stats, state)
        return
    for n in state.list_nodes(limit):
        mark = "up" if n["Alive"] else "DOWN"
        if n["Alive"] and n.get("Draining"):
            mark = "DRAINING"  # preemption notice received; node departing
        elif not n["Alive"] and n.get("Fenced"):
            # Declared dead, then heard from again (healed partition):
            # its RPCs are being rejected until it re-registers fresh.
            mark = "FENCED"
        labels = n.get("Labels") or {}
        slice_info = ""
        if labels.get("slice_name"):
            # Accelerator autodetection (or the provider) stamped slice
            # identity: show where each host sits in its pod slice.
            slice_info = (
                f" slice={labels['slice_name']}"
                f"[{labels.get('worker_index', 0)}]"
            )
            if labels.get("tpu_topology"):
                slice_info += f" topology={labels['tpu_topology']}"
        epoch_info = f" epoch={n['Epoch']}" if n.get("Epoch") is not None else ""
        pool_info = ""
        if getattr(args, "verbose", False):
            # Warm-pool health (rides the heartbeat stats): inventory vs
            # the forecast-sized target, plus the hit/miss counters that
            # say whether launches are going warm.
            p = (n.get("Stats") or {}).get("pool") or {}
            if p:
                hits = p.get("hits") or {}
                misses = p.get("misses") or {}
                pool_info = (
                    f" pool={p.get('ready', 0)}/{p.get('target', 0)}"
                    f"(+{p.get('preforked', 0)}pf)"
                    f" hits={sum(hits.values())} misses={sum(misses.values())}"
                )
                if not p.get("zygote_alive", True):
                    pool_info += " zygote=DOWN"
                elif p.get("zygote_respawns"):
                    pool_info += f" zygote_respawns={p['zygote_respawns']}"
        print(
            f"  [{mark}] {n['NodeID'][:12]}{epoch_info} resources={n['Resources']} "
            f"available={n['Available']} workers={n['Stats'].get('num_workers', 0)}"
            f"{pool_info}{slice_info}"
        )
    _status_tail(stats, state)


def _status_tail(stats, state) -> None:
    """The node-independent half of `ray-tpu status` (tasks, store,
    recovery/efficiency/LLM gauges, alerts, errors) — shared by the
    per-node and summary-only renderings."""
    print(f"tasks: {stats['tasks']}")
    print(f"actors: {stats['actors']}")
    s = stats["store"]
    print(
        f"object store: {s['num_objects']} objects, "
        f"{s['bytes_in_use'] / (1 << 20):.1f} MiB in use, {s['num_spilled']} spilled"
    )
    # Recovery counters: has this cluster actually been surviving
    # failures? (actor restarts, task retries, drains, restores — plus
    # chaos injections when a fault campaign is armed.)
    recovery = {
        "raytpu_actor_restarts_total": "actor_restarts",
        "raytpu_tasks_retried_total": "tasks_retried",
        "raytpu_nodes_drained_total": "nodes_drained",
        "raytpu_checkpoints_restored_total": "checkpoints_restored",
        "raytpu_chaos_injections_total": "chaos_injections",
    }
    totals = {label: 0.0 for label in recovery.values()}
    try:
        metrics_records = state.internal_metrics()
    except Exception:
        metrics_records = []
    try:
        for m in metrics_records:
            label = recovery.get(m.get("name"))
            if label:
                totals[label] += float(m.get("value") or 0.0)
    except Exception:
        totals = {}
    if totals:
        print(
            "recovery: "
            + " ".join(f"{k}={int(v)}" for k, v in totals.items())
        )
    # Efficiency gauges: is the hardware earning its keep? (goodput =
    # productive fraction of training wall time; MFU + tokens/s mirrored
    # from train.report.) Entries whose reporters were all pruned keep a
    # 0.0 table value forever — skip them, don't report a dead run as
    # "goodput=0.000". Reuses the metrics fetched for the recovery line.
    eff = {}
    try:
        for m in metrics_records:
            if m.get("kind") == "gauge" and not m.get("gauges"):
                continue
            name, val = m.get("name"), float(m.get("value") or 0.0)
            if name == "raytpu_train_goodput":
                eff["goodput"] = min(eff.get("goodput", 1.0), val)
            elif name == "raytpu_train_mfu":
                eff.setdefault("mfu", []).append(val)
            elif name == "raytpu_train_tokens_per_s":
                eff["tokens_per_s"] = eff.get("tokens_per_s", 0.0) + val
    except Exception:
        eff = {}
    if eff:
        parts = []
        if "goodput" in eff:
            parts.append(f"goodput={eff['goodput']:.3f}")
        if eff.get("mfu"):
            parts.append(f"mfu={sum(eff['mfu']) / len(eff['mfu']):.3f}")
        if "tokens_per_s" in eff:
            parts.append(f"tokens/s={eff['tokens_per_s']:g}")
        if parts:
            print("efficiency: " + " ".join(parts))
    # LLM serving gauges (serve/llm engine): decode throughput, KV page
    # pool occupancy, prefix-cache effectiveness, shed count. Only
    # printed when an LLM deployment has reported (pool total > 0).
    llm = {"tok_s": 0.0, "used": 0.0, "total": 0.0, "hits": 0.0, "miss": 0.0, "shed": 0.0}
    llm_names = {
        "raytpu_serve_tokens_per_s": "tok_s",
        "raytpu_kv_pages_used": "used",
        "raytpu_kv_pages_total": "total",
        "raytpu_prefix_cache_hits_total": "hits",
        "raytpu_prefix_cache_misses_total": "miss",
        "raytpu_serve_requests_shed_total": "shed",
    }
    try:
        for m in metrics_records:
            label = llm_names.get(m.get("name"))
            if label:
                llm[label] += float(m.get("value") or 0.0)
    except Exception:
        llm = {}
    if llm and llm["total"] > 0:
        lookups = llm["hits"] + llm["miss"]
        hit_pct = (llm["hits"] / lookups * 100.0) if lookups else 0.0
        print(
            f"llm serve: tokens/s={llm['tok_s']:g} "
            f"kv_pages={int(llm['used'])}/{int(llm['total'])} "
            f"prefix_hits={hit_pct:.0f}% shed={int(llm['shed'])}"
        )
    # Streaming data plane: live operator pools, bytes queued at operator
    # inputs, and backpressure edges. Only printed when a pipeline has
    # reported (some data metric is non-zero).
    dp = {"pool": 0.0, "queued": 0.0, "bp": 0.0, "tasks": 0.0}
    dp_names = {
        "raytpu_data_op_pool_size": "pool",
        "raytpu_data_op_queued_bytes": "queued",
        "raytpu_data_backpressure_total": "bp",
        "raytpu_data_op_tasks_total": "tasks",
    }
    try:
        for m in metrics_records:
            label = dp_names.get(m.get("name"))
            if label:
                dp[label] += float(m.get("value") or 0.0)
    except Exception:
        dp = {}
    if dp and any(dp.values()):
        print(
            f"data plane: pool_actors={int(dp['pool'])} "
            f"queued={int(dp['queued'])}B "
            f"backpressure_edges={int(dp['bp'])} tasks={int(dp['tasks'])}"
        )
    # Active SLO alerts (observability/watchdog.py): the reactive layer's
    # current verdict on the cluster.
    try:
        alerts = state.active_alerts()
    except Exception:
        alerts = []
    if alerts:
        for a in alerts:
            print(
                f"ALERT {a['rule']}: {a['metric']} {a.get('stat', 'value')}="
                f"{a['value']:g} {a['op']} {a['threshold']:g}"
                + (f" — {a['description']}" if a.get("description") else "")
            )
    else:
        print("alerts: none")
    # Recent cluster errors (uncaught worker exceptions / crashes fed by
    # the error-report pubsub): the "what broke" pointer next to the
    # metrics. Full records via state.cluster_errors() / `ray-tpu logs`.
    try:
        errors = state.cluster_errors(50)
    except Exception:
        errors = []
    if errors:
        print(f"errors: {len(errors)} recent (newest last)")
        for e in errors[-3:]:
            who = str(e.get("actor_id") or e.get("task") or e.get("worker_id") or "?")
            print(
                f"  [{e.get('type', 'error')}] node={str(e.get('node_id') or '?')[:8]} "
                f"{who[:40]}: {str(e.get('error', ''))[:120]}"
            )


_CLUSTER_STATE_DIR = os.path.expanduser("~/.ray_tpu/clusters")


def _load_cluster_config(path: str) -> dict:
    """Cluster-config YAML (reference: the `ray up` cluster YAML,
    autoscaler/ray-schema.json — collapsed to the fields the TPU launcher
    needs). JSON is valid YAML, so a .json config works too."""
    with open(path) as f:
        text = f.read()
    try:
        import yaml

        cfg = yaml.safe_load(text)
    except ImportError:
        cfg = json.loads(text)
    if not isinstance(cfg, dict):
        raise SystemExit(f"{path}: cluster config must be a mapping")
    cfg.setdefault("cluster_name", "ray-tpu")
    provider = cfg.setdefault("provider", {})
    ptype = provider.setdefault("type", "local")
    if ptype not in ("local", "gce_tpu"):
        raise SystemExit(f"{path}: provider.type must be 'local' or 'gce_tpu'")
    if ptype == "gce_tpu":
        for key in ("project_id", "zone"):
            if not provider.get(key):
                raise SystemExit(f"{path}: provider.{key} is required for gce_tpu")
        if not (cfg.get("workers") or {}).get("accelerator_type"):
            # The pod type IS the slice geometry on Cloud TPU; silently
            # substituting a default would provision the wrong hardware.
            raise SystemExit(
                f"{path}: workers.accelerator_type is required for gce_tpu "
                "(e.g. v5litepod-16)"
            )
    cfg.setdefault("head", {})
    workers = cfg.setdefault("workers", {})
    workers.setdefault("count", 1)
    return cfg


def _cluster_state_path(name: str) -> str:
    return os.path.join(_CLUSTER_STATE_DIR, f"{name}.json")


def _worker_shape(cfg: dict) -> dict:
    w = cfg["workers"]
    shape = {
        "cpus": float(w.get("cpus", 2.0)),
        "tpus": float(w.get("tpus", 0.0)),
        "slice_hosts": int(w.get("slice_hosts", 1)),
    }
    if w.get("accelerator_type"):
        shape["accelerator_type"] = w["accelerator_type"]
        # Declared pod type implies the slice geometry; fill what the
        # config leaves implicit so providers and status agree.
        from .accelerators import parse_pod_type

        parsed = parse_pod_type(w["accelerator_type"])
        if parsed is not None:
            _version, _total, chips_per_host, hosts = parsed
            if "tpus" not in w:
                shape["tpus"] = float(chips_per_host)
            if "slice_hosts" not in w:
                shape["slice_hosts"] = hosts
    if w.get("runtime_version"):
        shape["runtime_version"] = w["runtime_version"]
    return shape


def cmd_up(args) -> None:
    """`ray-tpu up cluster.yaml`: brings a cluster to the configured size
    through the autoscaler-v2 reconciler (reference: `ray up` driving the
    v2 instance manager). provider.type=local starts real raylet
    subprocesses on this machine; gce_tpu creates TPU pod slices over the
    Cloud TPU REST API — atomically, one slice per worker entry."""
    from .autoscaler_v2 import InstanceManager

    cfg = _load_cluster_config(args.config)
    name = cfg["cluster_name"]
    provider_cfg = cfg["provider"]
    shape = _worker_shape(cfg)
    count = int(cfg["workers"]["count"])
    os.makedirs(_CLUSTER_STATE_DIR, exist_ok=True)
    state_path = _cluster_state_path(name)
    if os.path.exists(state_path) and not args.force:
        raise SystemExit(
            f"cluster {name!r} already has recorded state ({state_path}); "
            "run `ray-tpu down` first or pass --force"
        )

    if provider_cfg["type"] == "local":
        from .accelerators import LocalNodeProvider
        from .core.cluster_runtime import Cluster
        from .core.rpc import RpcClient

        head = cfg["head"]
        cluster = Cluster(
            num_cpus=head.get("num_cpus"),
            num_tpus=head.get("num_tpus"),
            head_port=head.get("port"),
            labels=head.get("labels"),
        )
        provider = LocalNodeProvider(cluster)
        im = InstanceManager(
            provider, gcs=RpcClient(cluster.gcs_sock), shape=shape
        )
        im.set_target(count)
        ok = im.wait_running(count, timeout=args.timeout)
        # Let in-flight allocations land before snapshotting: a raylet
        # spawned by a provider thread AFTER pids.json is written would
        # escape both the pid record and `ray-tpu down`.
        quiesce = time.monotonic() + 15.0
        while (
            any(s == "pending" for s in provider.poll().values())
            and time.monotonic() < quiesce
        ):
            time.sleep(0.2)
        # Detach AFTER the wait so pids.json captures every raylet the
        # provider spawned while scaling up.
        _detach_cluster(cluster)
        state = {
            "type": "local",
            "cluster_name": name,
            "session_dir": cluster.session_dir,
            "cloud_ids": [
                i.cloud_id for i in im.instances.values() if i.cloud_id
            ],
        }
        with open(state_path, "w") as f:
            json.dump(state, f)
        running = im.counts().get("RAY_RUNNING", 0)
        print(
            f"cluster {name!r} up: head + {running}/{count} worker instances "
            f"(session dir: {cluster.session_dir})"
        )
        print(f"connect with: ray_tpu.init(address={cluster.session_dir!r})")
        if not ok:
            raise SystemExit(1)
        return

    provider = _gce_provider(cfg)
    im = InstanceManager(
        provider,
        shape=shape,
        # Cloud TPU slice allocation is minutes-long; the reconciler must
        # not time a REQUESTED slice out under it.
        request_timeout_s=max(600.0, args.timeout),
    )
    im.set_target(count)

    def record_state() -> list:
        cloud_ids = [i.cloud_id for i in im.instances.values() if i.cloud_id]
        with open(state_path, "w") as f:
            json.dump(
                {
                    "type": "gce_tpu",
                    "cluster_name": name,
                    "project_id": provider_cfg["project_id"],
                    "zone": provider_cfg["zone"],
                    "cloud_ids": cloud_ids,
                },
                f,
            )
        return cloud_ids

    # Issue the create calls, then record state BEFORE the (minutes-long)
    # allocation wait: a Ctrl-C mid-wait must leave `ray-tpu down` a
    # record of every slice already billing.
    im.reconcile()
    record_state()
    try:
        # Slice allocation is minutes-long; a gentle poll interval keeps
        # the Cloud TPU LIST quota (order 100 reads/min) untouched.
        ok = im.wait_allocated(count, timeout=args.timeout, interval=5.0)
    finally:
        cloud_ids = record_state()
    c = im.counts()
    print(
        f"cluster {name!r}: {c.get('ALLOCATED', 0) + c.get('RAY_RUNNING', 0)}"
        f"/{count} slices allocated ({', '.join(cloud_ids) or 'none'})"
    )
    if not ok:
        print("warning: not all slices came up before the timeout", file=sys.stderr)
        raise SystemExit(1)


def _gce_provider(cfg: dict):
    from .accelerators import GceTpuNodeProvider

    provider_cfg = cfg["provider"]
    workers = cfg["workers"]
    return GceTpuNodeProvider(
        provider_cfg["project_id"],
        provider_cfg["zone"],
        accelerator_type=workers.get("accelerator_type", "v5litepod-8"),
        runtime_version=workers.get("runtime_version", "tpu-ubuntu2204-base"),
        cluster_name=cfg["cluster_name"],
        head_address=provider_cfg.get("head_address"),
        startup_script=cfg.get("setup_script", ""),
    )


def cmd_down(args) -> None:
    """`ray-tpu down cluster.yaml`: terminates everything `up` recorded."""
    cfg = _load_cluster_config(args.config)
    name = cfg["cluster_name"]
    state_path = _cluster_state_path(name)
    try:
        with open(state_path) as f:
            state = json.load(f)
    except OSError:
        raise SystemExit(f"no recorded state for cluster {name!r} ({state_path})")
    if state["type"] == "local":
        ns = argparse.Namespace(address=state["session_dir"])
        try:
            cmd_stop(ns)
        except SystemExit:
            pass
    else:
        # Teardown targets what the STATE recorded, not what the YAML says
        # now: an edited project/zone would make every DELETE a 404
        # (treated as already-gone) and silently leak billing slices.
        from .accelerators import GceTpuNodeProvider

        provider = GceTpuNodeProvider(
            state["project_id"], state["zone"], cluster_name=name
        )
        for cloud_id in state.get("cloud_ids", []):
            try:
                provider.terminate(cloud_id)
                print(f"deleted {cloud_id}")
            except Exception as e:
                print(f"warning: failed to delete {cloud_id}: {e}", file=sys.stderr)
    try:
        os.unlink(state_path)
    except OSError:
        pass
    print(f"cluster {name!r} down")


def cmd_submit(args) -> None:
    import shlex

    from .jobs import JobSubmissionClient

    if args.address and args.address.startswith(("http://", "https://")):
        # Remote submission over the dashboard's REST job API — no cluster
        # attach needed (reference: `ray job submit --address http://...`).
        client = JobSubmissionClient(args.address)
    else:
        _connect(args)
        client = JobSubmissionClient()
    parts = list(args.entrypoint)
    if parts and parts[0] == "--":  # argparse.REMAINDER keeps the separator
        parts = parts[1:]
    entrypoint = " ".join(shlex.quote(p) for p in parts)
    job_id = client.submit_job(entrypoint=entrypoint)
    print(f"submitted {job_id}: {entrypoint}")
    if args.wait:
        status = client.wait_until_finished(job_id, timeout=args.timeout)
        print(f"{job_id}: {status}")
        sys.stdout.write(client.get_job_logs(job_id))
        if status != "SUCCEEDED":
            raise SystemExit(1)


def cmd_jobs(args) -> None:
    _connect(args)
    from .jobs import JobSubmissionClient

    for rec in JobSubmissionClient().list_jobs():
        print(f"{rec['job_id']}  {rec['status']:<10} {rec['entrypoint']}")


def cmd_logs(args) -> None:
    """`ray-tpu logs`: query the cluster's structured log stream
    (per-process JSONL session logs + captured worker stdout/stderr,
    merged across nodes by the raylet `tail_logs` fan-out). With a
    positional job id, prints that job's captured output instead."""
    _connect(args)
    if getattr(args, "job_id", None):
        from .jobs import JobSubmissionClient

        sys.stdout.write(JobSubmissionClient().get_job_logs(args.job_id))
        return
    from .observability import logs as obslogs
    from .utils import state

    actor = args.actor
    if actor:
        # Accept an actor NAME as well as an id prefix.
        try:
            for a in state.list_actors(100_000):
                if a.get("name") == actor:
                    actor = a["actor_id"]
                    break
        except Exception:  # lint: swallow-ok(name lookup is optional sugar; id prefix still works)
            pass
    filters = {
        "component": args.component,
        "level": args.level,
        "task_id": args.task,
        "actor_id": actor,
        "grep": args.grep,
    }
    filters = {k: v for k, v in filters.items() if v}
    since = None
    # Follow mode re-polls with a 5 s OVERLAP window + client-side dedup
    # instead of a strict high-water cursor: one node's tail_logs RPC
    # failing (silently skipped by the fan-out) or lagging the fastest
    # node's timestamps must not permanently drop its records.
    seen: dict = {}
    overlap_s = 5.0
    try:
        while True:
            recs = state.cluster_logs(
                node=args.node,
                tail=args.tail if since is None else None,
                since_ts=(since - overlap_s) if since is not None else None,
                **filters,
            )
            for r in recs:
                key = (r.get("ts"), r.get("pid"), r.get("node_id"), r.get("msg"))
                if key in seen:
                    continue
                seen[key] = r.get("ts") or 0.0
                print(obslogs.format_record(r))
            if not args.follow:
                return
            if recs:
                since = max(
                    since or 0.0, max(float(r.get("ts") or 0.0) for r in recs)
                )
            elif since is None:
                since = time.time()
            if since is not None:
                cutoff = since - 2 * overlap_s
                for key in [k for k, ts in seen.items() if ts < cutoff]:
                    del seen[key]
            time.sleep(1.0)
    except KeyboardInterrupt:
        return


def format_metrics_table(sections) -> str:
    """Renders aggregated metric records as one aligned table with a
    header; `sections` is [(source, records), ...] (shared by
    `ray-tpu metrics` and its test)."""
    rows = [("SOURCE", "NAME", "KIND", "TAGS", "VALUE")]
    for source, records in sections:
        for m in sorted(
            records, key=lambda r: (r.get("name", ""), str(r.get("tags")))
        ):
            tags = m.get("tags") or {}
            tag_str = ",".join(f"{k}={v}" for k, v in sorted(tags.items()))
            val = m.get("value", 0.0)
            if m.get("kind") == "histogram":
                count = sum(m.get("counts") or [])
                val = f"sum={val:g} count={count}"
            else:
                val = f"{val:g}"
            rows.append(
                (source, m.get("name", "?"), m.get("kind", "?"), tag_str, val)
            )
    # Header participates in the width computation so it stays aligned.
    widths = [max(len(r[i]) for r in rows) for i in range(4)]
    return "\n".join(
        "  ".join(col.ljust(w) for col, w in zip(r[:4], widths)) + "  " + r[4]
        for r in rows
    )


def _filter_records(records, pattern):
    if not pattern:
        return records
    return [r for r in records if pattern in (r.get("name") or "")]


def _metric_key(m) -> tuple:
    return (m.get("name"), tuple(sorted((m.get("tags") or {}).items())))


def _cumulative_value(m) -> float:
    if m.get("kind") == "histogram":
        return float(sum(m.get("counts") or []))
    return float(m.get("value") or 0.0)


def format_watch_table(cur, prev, dt: float) -> str:
    """One tick of `ray-tpu metrics --watch`: per series, the current
    value plus the per-second rate since the previous snapshot
    (counters/histograms; gauges show their value — rate of a level is
    noise). `prev` maps _metric_key -> cumulative value; "-" marks
    series with no previous snapshot yet."""
    rows = [("NAME", "KIND", "TAGS", "VALUE", "RATE/S")]
    for m in sorted(cur, key=lambda r: (r.get("name", ""), str(r.get("tags")))):
        tags = m.get("tags") or {}
        tag_str = ",".join(f"{k}={v}" for k, v in sorted(tags.items()))
        kind = m.get("kind", "?")
        value = _cumulative_value(m)
        if kind == "gauge":
            rate = ""
        else:
            before = prev.get(_metric_key(m))
            rate = (
                f"{(value - before) / dt:+.6g}"
                if before is not None and dt > 0
                else "-"
            )
        rows.append((m.get("name", "?"), kind, tag_str, f"{value:g}", rate))
    widths = [max(len(r[i]) for r in rows) for i in range(4)]
    return "\n".join(
        "  ".join(col.ljust(w) for col, w in zip(r[:4], widths)) + "  " + r[4]
        for r in rows
    )


def cmd_metrics(args) -> None:
    _connect(args)
    from .utils import state

    pattern = getattr(args, "filter", None)
    if not getattr(args, "watch", False):
        internal = _filter_records(state.internal_metrics(), pattern)
        user = _filter_records(state.user_metrics(), pattern)
        print(format_metrics_table([("internal", internal), ("user", user)]))
        print(f"\n{len(internal)} internal + {len(user)} user metric series")
        return
    # --watch: tail rates instead of printing one snapshot. Counters and
    # histogram counts show deltas/s against the previous tick.
    prev: dict = {}
    prev_ts = None
    n = 0
    while True:
        records = _filter_records(
            state.internal_metrics() + state.user_metrics(), pattern
        )
        now = time.monotonic()
        dt = (now - prev_ts) if prev_ts is not None else 0.0
        if n and sys.stdout.isatty():
            print("\x1b[2J\x1b[H", end="")
        print(format_watch_table(records, prev, dt))
        print(f"\n[{time.strftime('%H:%M:%S')}] {len(records)} series; ctrl-c to stop")
        prev = {_metric_key(m): _cumulative_value(m) for m in records}
        prev_ts = now
        n += 1
        if args.iterations and n >= args.iterations:
            return
        try:
            time.sleep(args.interval)
        except KeyboardInterrupt:
            return


# ----------------------------------------------------------------- `top`
# (label, metric, mode, scale, unit, cross-series agg)
TOP_SIGNALS = [
    ("tasks/s", "raytpu_sched_dispatch_latency_ms", "rate", 1.0, "/s", "sum"),
    ("gcs rpc/s", "raytpu_gcs_rpc_total", "rate", 1.0, "/s", "sum"),
    ("pubsub backlog", "raytpu_gcs_pubsub_backlog", "value", 1.0, "", "sum"),
    ("cgraph MB/s", "raytpu_cgraph_channel_bytes_total", "rate", 1e-6, "MB/s", "sum"),
    ("device HBM MiB", "raytpu_device_mem_used_bytes", "value", 1.0 / (1 << 20), "MiB", "sum"),
    ("node cpu %", "raytpu_node_cpu_percent", "value", 1.0, "%", "mean"),
    ("heartbeat lag s", "raytpu_node_heartbeat_lag_s", "value", 1.0, "s", "max"),
    ("actor restarts", "raytpu_actor_restarts_total", "value", 1.0, "", "sum"),
    ("nodes drained", "raytpu_nodes_drained_total", "value", 1.0, "", "sum"),
    ("train goodput", "raytpu_train_goodput", "value", 1.0, "", "mean"),
    ("serve req/s", "raytpu_serve_requests_total", "rate", 1.0, "/s", "sum"),
    ("serve tok/s", "raytpu_serve_tokens_per_s", "value", 1.0, "/s", "sum"),
    ("kv pages used", "raytpu_kv_pages_used", "value", 1.0, "", "sum"),
    ("serve shed", "raytpu_serve_requests_shed_total", "value", 1.0, "", "sum"),
]

_SPARK_BLOCKS = "▁▂▃▄▅▆▇█"


def sparkline(values, width: int = 32) -> str:
    """Unicode block sparkline of the last `width` values, scaled to the
    window's own min..max (a flat line is a flat line, not noise)."""
    vals = list(values)[-width:]
    if not vals:
        return ""
    if max(vals) == min(vals):
        # Constant signal: a flat mid line, not a wall of full blocks.
        return ("▄" if vals[0] else _SPARK_BLOCKS[0]) * len(vals)
    lo = min(min(vals), 0.0)  # rates anchor at zero, not the window min
    hi = max(vals)
    span = hi - lo
    return "".join(
        _SPARK_BLOCKS[min(7, int((v - lo) / span * 8))] for v in vals
    )


def render_top(fetch, alerts, window_s: float = 120.0, width: int = 32) -> str:
    """The `ray-tpu top` frame: per key signal, current value +
    sparkline over the history window. `fetch(metric, as_rate)` returns
    history series (injected for tests); `alerts` is the active-alert
    list rendered on top."""
    from .observability.history import merge_series

    lines = []
    if alerts:
        for a in alerts:
            lines.append(
                f"ALERT {a['rule']}: {a['metric']}={a['value']:g} "
                f"{a['op']} {a['threshold']:g}"
            )
    else:
        lines.append("alerts: none")
    bucket_s = max(1.0, window_s / width)
    for label, metric, mode, scale, unit, agg in TOP_SIGNALS:
        try:
            series = fetch(metric, mode == "rate")
        except Exception:
            series = []
        merged = merge_series(series, bucket_s=bucket_s, agg=agg)
        if not merged:
            lines.append(f"{label:<18} {'-':>12}       (no data)")
            continue
        values = [v * scale for _, v in merged]
        lines.append(
            f"{label:<18} {values[-1]:>12.6g}{unit:<5} {sparkline(values, width)}"
        )
    return "\n".join(lines)


def cmd_top(args) -> None:
    """`ray-tpu top`: live rates + sparklines for the key cluster
    signals, straight off the GCS metrics-history rings."""
    _connect(args)
    from .utils import state

    n = 0
    while True:
        def fetch(metric, as_rate):
            return state.metrics_history(
                metric, None, args.window, as_rate
            )

        try:
            alerts = state.active_alerts()
        except Exception:
            alerts = []
        frame = render_top(fetch, alerts, window_s=args.window)
        if n and sys.stdout.isatty():
            print("\x1b[2J\x1b[H", end="")
        print(frame)
        print(f"\n[{time.strftime('%H:%M:%S')}] window={args.window:g}s; ctrl-c to stop")
        n += 1
        if args.iterations and n >= args.iterations:
            return
        try:
            time.sleep(args.interval)
        except KeyboardInterrupt:
            return


def cmd_timeline(args) -> None:
    _connect(args)
    from .utils import state

    events = state.timeline(args.out)
    n_spans = sum(
        1 for e in events if str(e.get("cat", "")).startswith("span")
    )
    n_open = sum(1 for e in events if e.get("tid") == "open at dump")
    extra = f" (+{n_spans} trace spans, {n_open} open at dump)" if n_spans else ""
    print(f"wrote {len(events)} task spans{extra} to {args.out} (open in Perfetto)")
    if not n_spans:
        print(
            "hint: run the workload with RAY_TPU_TRACING=1 to include "
            "runtime spans (actor-launch phase breakdown)"
        )


def cmd_trace(args) -> None:
    """`ray-tpu trace --out trace.json`: the full Perfetto merge — every
    process's tracing spans, flight-recorder dumps, the GCS task table,
    and internal-metrics counter tracks, with submit->schedule->execute
    and request->replica->response flow arrows."""
    _connect(args)
    from .observability import perfetto
    from .utils import state

    task_events = state.task_timeline_events()
    try:
        metrics = state.internal_metrics()
    except Exception:
        metrics = []
    try:
        # Log records merge as instants on the emitting process's track;
        # trace_id-linked lines land inside that request's spans.
        log_records = state.cluster_logs(tail=20_000)
    except Exception:
        log_records = []
    result = perfetto.export(
        path=args.out,
        task_events=task_events,
        metrics=metrics,
        log_records=log_records,
    )
    s = result["summary"]
    print(
        f"wrote {s['events']} events to {args.out} "
        f"({s['spans']} spans, {s['flows']} flow arrows, "
        f"{s['flight_dumps']} flight dumps, {s.get('profiles', 0)} profiles, "
        f"{s.get('log_records', 0)} log records, "
        f"{s['task_events']} task rows) — open at ui.perfetto.dev"
    )
    if not s["spans"]:
        print(
            "hint: run the workload with RAY_TPU_TRACING=1 to record "
            "spans; the flight recorder is always on"
        )


def cmd_debug(args) -> None:
    """`ray-tpu debug dump`: flight-recorder post-mortem on demand — every
    raylet dumps its ring and fans SIGUSR2 out to its workers (their
    handlers dump too); the driver CLI dumps its own.
    `ray-tpu debug profile --seconds N`: every raylet runs its in-process
    sampling profiler for N seconds and dumps hottest-stacks JSON+text
    under the profile dir (merged by `ray-tpu trace`)."""
    if args.action == "profile":
        _connect(args)
        from .core.rpc import RpcClient
        from .utils import state
        from .utils.sampling_profiler import profile_dir

        from concurrent.futures import ThreadPoolExecutor

        alive = [n for n in state.list_nodes() if n.get("Alive")]

        def one(n):
            return RpcClient(n["sock"], connect_timeout=5.0).call(
                "profile", args.seconds, timeout=args.seconds + 30.0
            )

        paths = []
        # Concurrent fan-out: every node samples the SAME window (a
        # sequential walk would offset each node's profile by the full
        # duration, defeating cross-node comparison) and the command
        # returns in ~seconds, not nodes x seconds. Pool bounded: a
        # thread per node stops scaling around a few hundred nodes
        # (thread-stack memory + connect storms on one CLI process).
        with ThreadPoolExecutor(max_workers=min(64, max(1, len(alive)))) as pool:
            for n, fut in [(n, pool.submit(one, n)) for n in alive]:
                try:
                    res = fut.result()
                except Exception as e:  # noqa: BLE001
                    print(
                        f"warning: node {n['NodeID'][:12]} profile failed: {e}",
                        file=sys.stderr,
                    )
                    continue
                if res.get("path"):
                    paths.append(res["path"])
                    print(
                        f"node {n['NodeID'][:12]}: {res['samples']} samples "
                        f"-> {res['path']}"
                    )
        print(f"wrote {len(paths)} profiles under {profile_dir()}")
        print("merge into a timeline with: ray-tpu trace --out trace.json")
        return
    if args.action != "dump":
        raise SystemExit(
            f"unknown debug action {args.action!r} (expected: dump | profile)"
        )
    _connect(args)
    from .core.rpc import RpcClient
    from .observability import flight_recorder
    from .utils import state

    # Dump the CLI's own ring first so the staged bundle picks it up
    # alongside the cluster-wide harvest.
    flight_recorder.dump(reason="debug dump (cli)")
    try:
        harvest = state._gcs().call("debug_harvest", timeout=45.0)
    except Exception as e:  # noqa: BLE001
        harvest = {"ok": False, "reason": repr(e)}
    if harvest.get("ok") and harvest.get("bundle"):
        print(
            f"incident {harvest['incident']} staged "
            f"({len(harvest.get('triggers', []))} trigger(s))"
        )
        print(f"bundle: {harvest['bundle']}")
        print(f"inspect with: ray-tpu postmortem {harvest['incident']}")
        return
    # Trigger bus disabled (RAY_TPU_POSTMORTEM=0) or the harvest failed:
    # fall back to the legacy loose per-node dump so the command still
    # yields artifacts.
    print(
        f"warning: incident harvest unavailable "
        f"({harvest.get('reason', 'unknown')}); falling back to raw dumps",
        file=sys.stderr,
    )
    dumped = []
    signaled = 0
    from concurrent.futures import ThreadPoolExecutor

    alive = [n for n in state.list_nodes() if n.get("Alive")]

    def _dump_one(n):
        return RpcClient(n["sock"], connect_timeout=5.0).call(
            "flight_dump", timeout=10.0
        )

    # Bounded concurrent fan-out: the sequential walk multiplied its 5 s
    # connect timeout by the node count — at 1000 nodes, over an hour of
    # worst case for a debug command.
    with ThreadPoolExecutor(max_workers=min(64, max(1, len(alive)))) as pool:
        for n, fut in [(n, pool.submit(_dump_one, n)) for n in alive]:
            try:
                res = fut.result()
            except Exception as e:  # noqa: BLE001
                print(
                    f"warning: node {n['NodeID'][:12]} dump failed: {e}",
                    file=sys.stderr,
                )
                continue
            if res.get("path"):
                dumped.append(res["path"])
            signaled += res.get("workers_signaled", 0)
    print(
        f"wrote {len(dumped)} flight-recorder dumps "
        f"(+{signaled} workers signaled) under {flight_recorder.flight_dir()}"
    )
    print("merge into a timeline with: ray-tpu trace --out trace.json")


def cmd_postmortem(args) -> None:
    """`ray-tpu postmortem [incident]`: renders the markdown incident
    report for one staged bundle — trigger chain, suspect channel/rank/
    node, last-N flight events per involved process (clock-skew
    corrected), goodput/MFU impact window. With no token it lists the
    staged bundles. Works offline: bundles are plain directories under
    `<session>/incidents/`, no live cluster needed."""
    from .observability import postmortem

    roots = []
    # The session dir's incidents/ when a cluster is (or recently was)
    # around...
    try:
        addr = _resolve_address(args)
        if addr and not addr.startswith("tcp://") and os.path.isdir(addr):
            roots.append(postmortem.incidents_dir(addr))
    except SystemExit:
        pass
    # ...plus the trace-dir fallback an in-process GCS stages under.
    default_root = postmortem.incidents_dir(None)
    if default_root not in roots:
        roots.append(default_root)

    if not args.incident:
        rows = [b for root in roots for b in postmortem.list_bundles(root)]
        if not rows:
            print(f"no incident bundles under {' or '.join(roots)}")
            return
        for b in rows:
            print(
                f"{b['incident_id']}  trigger={b['trigger']}  "
                f"triggers={b['triggers']}  nodes={b['nodes']}  {b['bundle']}"
            )
        print("render one with: ray-tpu postmortem <incident>")
        return
    bundle = postmortem.find_bundle(args.incident, roots)
    if bundle is None:
        raise SystemExit(
            f"no unique incident matches {args.incident!r} under "
            f"{' or '.join(roots)} (run `ray-tpu postmortem` to list)"
        )
    report = postmortem.render_report(bundle, last_n=args.last)
    if args.out:
        with open(args.out, "w") as f:
            f.write(report)
        print(f"wrote {args.out}")
    else:
        print(report)


def cmd_dashboard(args) -> None:
    _connect(args)
    from .dashboard import start_dashboard

    port = start_dashboard(port=args.port)
    print(f"dashboard at http://127.0.0.1:{port}/ (ctrl-c to stop)")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="ray-tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("start", help="start a cluster head (or join one with --address)")
    p.add_argument("--num-cpus", type=float, default=None)
    p.add_argument("--num-tpus", type=float, default=None)
    p.add_argument("--resources", default=None, help="JSON dict of custom resources")
    p.add_argument("--object-store-memory", type=int, default=None)
    p.add_argument(
        "--port",
        type=int,
        default=None,
        help="also serve the GCS on tcp://<node-ip>:<port> so other hosts can join (0 = ephemeral)",
    )
    p.add_argument(
        "--node-ip-address",
        default=None,
        help="routable ip this host advertises to the cluster "
        "(default: 127.0.0.1 for a head; derived from the route to the "
        "GCS when joining with --address)",
    )
    p.add_argument(
        "--address",
        default=None,
        help="join an existing cluster: the head's tcp://host:port GCS endpoint",
    )
    p.add_argument(
        "--labels",
        default=None,
        help="JSON dict of node labels (e.g. slice identity or the "
        "provider's cloud-id stamp)",
    )
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser(
        "up", help="bring a cluster to its configured size from a cluster-config YAML"
    )
    p.add_argument("config", help="cluster-config YAML (or JSON) path")
    p.add_argument("--timeout", type=float, default=900.0)
    p.add_argument(
        "--force", action="store_true", help="ignore existing recorded state"
    )
    p.set_defaults(fn=cmd_up)

    p = sub.add_parser("down", help="terminate a cluster started with `ray-tpu up`")
    p.add_argument("config", help="the same cluster-config YAML given to `up`")
    p.set_defaults(fn=cmd_down)

    p = sub.add_parser("stop", help="stop the cluster")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_stop)

    p = sub.add_parser("status", help="cluster nodes/tasks/store summary")
    p.add_argument(
        "--verbose",
        action="store_true",
        help="per-node worker-pool column (ready/target, preforks, hit/miss)",
    )
    p.add_argument(
        "--summary",
        action="store_true",
        help="aggregate rollup only, no per-node rows (the sane view at "
        "hundreds of nodes; auto-engaged above %d nodes)" % _STATUS_AUTO_SUMMARY,
    )
    p.add_argument(
        "--limit",
        type=int,
        default=None,
        help="cap the per-node rows printed (node-id order)",
    )
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_status)

    p = sub.add_parser("submit", help="submit a job entrypoint command")
    p.add_argument("--address", default=None)
    p.add_argument("--wait", action="store_true", help="block until the job finishes")
    p.add_argument("--timeout", type=float, default=600.0)
    p.add_argument("entrypoint", nargs=argparse.REMAINDER)
    p.set_defaults(fn=cmd_submit)

    p = sub.add_parser("jobs", help="list submitted jobs")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_jobs)

    p = sub.add_parser(
        "logs",
        help="query cluster logs (structured records + captured worker "
        "output); with a job id, print that job's output",
    )
    p.add_argument("--address", default=None)
    p.add_argument("job_id", nargs="?", default=None)
    p.add_argument("--node", default=None, help="node id prefix filter")
    p.add_argument(
        "--actor", default=None, help="actor id prefix or actor name"
    )
    p.add_argument("--task", default=None, help="task id prefix filter")
    p.add_argument(
        "--component",
        default=None,
        help="component filter (e.g. raylet, worker, serve, stdout, stderr)",
    )
    p.add_argument(
        "--level", default=None, help="minimum level (DEBUG/INFO/WARNING/ERROR)"
    )
    p.add_argument("--grep", default=None, help="substring filter on messages")
    p.add_argument(
        "--follow",
        "-f",
        action="store_true",
        help="keep polling for new records (ctrl-c to stop)",
    )
    p.add_argument(
        "--tail", type=int, default=100, help="show only the newest N records"
    )
    p.set_defaults(fn=cmd_logs)

    p = sub.add_parser(
        "metrics", help="dump current internal + user metrics as a table"
    )
    p.add_argument("--address", default=None)
    p.add_argument(
        "--filter", default=None, help="only metrics whose name contains this"
    )
    p.add_argument(
        "--watch",
        action="store_true",
        help="tail metric rates (deltas/s per tick) instead of one snapshot",
    )
    p.add_argument("--interval", type=float, default=2.0)
    p.add_argument(
        "--iterations",
        type=int,
        default=0,
        help="stop --watch after N ticks (0 = until ctrl-c)",
    )
    p.set_defaults(fn=cmd_metrics)

    p = sub.add_parser(
        "top",
        help="live cluster signals: rates + sparklines from metrics history",
    )
    p.add_argument("--address", default=None)
    p.add_argument("--interval", type=float, default=2.0)
    p.add_argument("--window", type=float, default=120.0)
    p.add_argument(
        "--iterations",
        type=int,
        default=0,
        help="stop after N frames (0 = until ctrl-c)",
    )
    p.set_defaults(fn=cmd_top)

    p = sub.add_parser("dashboard", help="serve the cluster dashboard")
    p.add_argument("--address", default=None)
    p.add_argument("--port", type=int, default=8265)
    p.set_defaults(fn=cmd_dashboard)

    p = sub.add_parser("timeline", help="export a chrome-trace of task spans")
    p.add_argument("--address", default=None)
    p.add_argument("--out", default="ray_tpu_timeline.json")
    p.set_defaults(fn=cmd_timeline)

    p = sub.add_parser(
        "trace",
        help="export the unified Perfetto trace (spans + flight rings + "
        "task table + metric counters, with flow arrows)",
    )
    p.add_argument("--address", default=None)
    p.add_argument("--out", default="trace.json")
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser(
        "debug",
        help="debug utilities: `debug dump` writes flight-recorder rings; "
        "`debug profile --seconds N` samples every raylet's stacks",
    )
    p.add_argument("action", help="dump | profile")
    p.add_argument("--address", default=None)
    p.add_argument(
        "--seconds",
        type=float,
        default=5.0,
        help="profile duration per node (profile action)",
    )
    p.set_defaults(fn=cmd_debug)

    p = sub.add_parser(
        "postmortem",
        help="render the markdown incident report for a staged bundle "
        "(no argument: list incident bundles)",
    )
    p.add_argument(
        "incident",
        nargs="?",
        default=None,
        help="incident id, unambiguous id prefix, or bundle dir path",
    )
    p.add_argument("--address", default=None)
    p.add_argument(
        "--out", default=None, help="write the report here instead of stdout"
    )
    p.add_argument(
        "--last",
        type=int,
        default=20,
        help="flight events shown per involved process",
    )
    p.set_defaults(fn=cmd_postmortem)

    args = ap.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
