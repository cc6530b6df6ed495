"""CLI entry: `python -m seaweedfs_tpu <subcommand>`.

Reference surface: weed/command/command.go (27 subcommands).  Implemented:
master, volume, server (master+volume), filer, shell, bench, version,
ec.encode (offline), fix (rebuild .idx from .dat), export, compact.
"""

from __future__ import annotations

import argparse
import sys
import time


def cmd_master(args) -> None:
    from .master.server import MasterServer
    from .util.config import load_configuration

    # TOML tier: master.toml supplies the maintenance script + sequencer
    # defaults; explicit CLI flags win (util/config.go two-tier model)
    mconf = load_configuration("master")
    interval = args.maintenanceInterval
    if interval is None:  # flag not given -> TOML, else 0 (disabled)
        interval = mconf.get_float("master.maintenance.periodic_seconds")
    # scripts=[] in the TOML means "run nothing", which run_maintenance
    # distinguishes from None (= its default suite)
    raw_scripts = mconf.get("master.maintenance.scripts")
    script = raw_scripts if isinstance(raw_scripts, list) else None
    sequencer = mconf.get_string("master.sequencer.type", "memory")
    node_id = mconf.get_int("master.sequencer.sequencer_snowflake_id")

    lifecycle_policy = None
    if args.lifecyclePolicy:
        import json

        with open(args.lifecyclePolicy) as f:
            lifecycle_policy = json.load(f)
    slo_specs = None
    if args.sloSpecs:
        from .telemetry.slo import specs_from_json

        slo_specs = specs_from_json(args.sloSpecs)
    m = MasterServer(
        ip=args.ip,
        port=args.port,
        volume_size_limit_mb=args.volumeSizeLimitMB,
        default_replication=args.defaultReplication,
        maintenance_interval=interval,
        maintenance_script=script,
        lifecycle_interval=args.lifecycleInterval,
        lifecycle_dir=args.lifecycleDir,
        lifecycle_rate_mbps=args.lifecycleRateMBps,
        lifecycle_policy=lifecycle_policy,
        repair_deadline_s=args.repairDeadlineS,
        sequencer=sequencer,
        sequencer_node_id=node_id,
        sequencer_etcd_urls=mconf.get_string(
            "master.sequencer.sequencer_etcd_urls", "127.0.0.1:2379"),
        metrics_port=args.metricsPort,
        jwt_signing_key=args.jwtKey or _security_jwt_key(),
        peers=args.peers.split(",") if args.peers else None,
        raft_state_dir=args.raftDir,
        peer_clusters=(args.peerClusters.split(",")
                       if args.peerClusters else None),
        slo_interval=args.sloInterval,
        slo_specs=slo_specs,
        canary_interval=args.canaryInterval,
        canary_s3=args.canaryS3,
        alert_webhook=args.alertWebhook,
        debug_dir=args.debugDir,
    )
    m.start()
    print(f"master listening http={args.port} grpc={m.grpc_port}")
    _wait()


def cmd_volume(args) -> None:
    from .util.config import load_configuration
    from .telemetry.interpreter import start_wait_probe
    from .util.hostmem import keep_freed_memory
    from .volume.server import VolumeServer

    keep_freed_memory()  # slices and readbacks reuse the heap's pages
    start_wait_probe()  # seaweedfs_interpreter_wait_seconds

    if getattr(args, "offset5", False):
        from .storage import types as _t

        _t.set_offset_size(5)
    if getattr(args, "index", "memory") != "memory":
        from .storage.volume import set_needle_map_kind

        set_needle_map_kind(args.index)
    codec = getattr(args, "ec_codec", "")
    if not codec:  # flag not given -> master.toml [codec].type, else cpu
        codec = load_configuration("master").get_string("codec.type", "cpu")
    v = VolumeServer(
        directories=args.dir.split(","),
        master_addresses=[
            _grpc_addr(m) for m in args.mserver.split(",")
        ],
        ip=args.ip,
        port=args.port,
        data_center=args.dataCenter,
        rack=args.rack,
        codec_name=codec,
        max_volume_count=args.max,
        metrics_port=args.metricsPort,
        jwt_signing_key=args.jwtKey or _security_jwt_key(),
        whitelist=(args.whiteList.split(",") if args.whiteList
                   else _security_white_list()),
        tier_backends=_load_tier_backends(args.tierBackends),
        tcp_port=args.tcpPort,
    )
    v.start()
    print(f"volume server http={args.port} grpc={v.grpc_port} dirs={args.dir}")
    _wait()


def cmd_server(args) -> None:
    """`weed server`: master + volume, optionally filer and s3 gateway in
    one process (command/server.go)."""
    from .master.server import MasterServer
    from .util.config import load_configuration
    from .telemetry.interpreter import start_wait_probe
    from .util.hostmem import keep_freed_memory
    from .volume.server import VolumeServer

    keep_freed_memory()  # slices and readbacks reuse the heap's pages
    start_wait_probe()  # seaweedfs_interpreter_wait_seconds
    codec = getattr(args, "ec_codec", "")
    if not codec:
        codec = load_configuration("master").get_string("codec.type", "cpu")
    m = MasterServer(ip=args.ip, port=args.masterPort)
    m.start()
    v = VolumeServer(
        directories=args.dir.split(","),
        master_addresses=[f"{args.ip}:{m.grpc_port}"],
        ip=args.ip,
        port=args.port,
        codec_name=codec,
    )
    v.start()
    extras = []
    if args.filer or args.s3:
        from .filer.server import FilerServer

        from .notification import publisher_from_config

        store, store_path, store_options = _filer_store_selection(
            args.filerStore)
        filer = FilerServer(
            masters=[f"{args.ip}:{m.grpc_port}"],
            ip=args.ip, port=args.filerPort, store=store,
            store_path=store_path, store_options=store_options,
            notification=publisher_from_config(
                load_configuration("notification")),
        )
        filer.start()
        extras.append(f"filer={args.filerPort}")
        if args.s3:
            from .s3api.server import S3ApiServer

            s3 = S3ApiServer(
                filer=f"{args.ip}:{args.filerPort}", port=args.s3Port,
                iam_config_filer_path="/etc/iam/identity.json",
            )
            s3.start()
            extras.append(f"s3={args.s3Port}")
    print(f"server: master={args.masterPort} volume={args.port}"
          + ("" if not extras else " " + " ".join(extras)))
    _wait()


def _filer_store_selection(flag_store: str) -> tuple[str, str, dict]:
    """filer.toml picks the store backend; the -store flag (a path)
    keeps its historical meaning of "sqlite at this path" and wins when
    given.  -> (store, store_path, store_options)."""
    from .util.config import load_configuration

    store, store_path, store_options = "sqlite", flag_store, {}
    fconf = load_configuration("filer")
    if fconf.loaded and flag_store == "./filer.db":  # flag left at default
        for kind, path_key in (("sqlite", "dbFile"), ("leveldb", "dir"),
                               ("leveldb2", "dir"), ("leveldb3", "dir"),
                               ("redis", ""), ("etcd", ""),
                               ("elastic7", ""), ("mongodb", ""),
                               ("cassandra", ""),
                               ("mysql", ""), ("postgres", ""),
                               ("memory", "")):
            if fconf.get_bool(f"{kind}.enabled"):
                store = kind
                if path_key:
                    store_path = fconf.get_string(
                        f"{kind}.{path_key}", store_path)
                break
        if store == "redis":
            store_options = {
                "host": fconf.get_string("redis.host", "127.0.0.1"),
                "port": fconf.get_int("redis.port", 6379),
                "db": fconf.get_int("redis.db", 0),
            }
        elif store == "etcd":
            store_options = {
                "servers": fconf.get_string("etcd.servers",
                                            "127.0.0.1:2379"),
            }
        elif store == "elastic7":
            store_options = {
                "servers": fconf.get_string("elastic7.servers",
                                            "http://127.0.0.1:9200"),
                "username": fconf.get_string("elastic7.username", ""),
                "password": fconf.get_string("elastic7.password", ""),
            }
        elif store == "mongodb":
            store_options = {
                "host": fconf.get_string("mongodb.host", "127.0.0.1"),
                "port": fconf.get_int("mongodb.port", 27017),
                "database": fconf.get_string("mongodb.database",
                                             "seaweedfs"),
            }
        elif store == "cassandra":
            store_options = {
                "host": fconf.get_string("cassandra.host", "127.0.0.1"),
                "port": fconf.get_int("cassandra.port", 9042),
                "keyspace": fconf.get_string("cassandra.keyspace",
                                             "seaweedfs"),
            }
        elif store in ("mysql", "postgres"):
            port_default = {"mysql": 3306, "postgres": 5432}[store]
            user_default = {"mysql": "root", "postgres": "postgres"}[store]
            store_options = {
                "hostname": fconf.get_string(f"{store}.hostname",
                                             "localhost"),
                "port": fconf.get_int(f"{store}.port", port_default),
                "username": fconf.get_string(f"{store}.username",
                                             user_default),
                "password": fconf.get_string(f"{store}.password", ""),
                "database": fconf.get_string(f"{store}.database",
                                             "seaweedfs"),
            }
    return store, store_path, store_options


def cmd_filer(args) -> None:
    from .filer.server import FilerServer
    from .notification import publisher_from_config
    from .util.config import load_configuration

    store, store_path, store_options = _filer_store_selection(args.store)
    notification = publisher_from_config(load_configuration("notification"))

    f = FilerServer(
        masters=[_grpc_addr(m) for m in args.master.split(",")],
        ip=args.ip,
        port=args.port,
        store=store,
        store_path=store_path,
        max_mb=args.maxMB,
        metrics_port=args.metricsPort,
        peers=args.peers.split(",") if args.peers else None,
        cipher=args.cipher,
        store_options=store_options,
        notification=notification,
        cluster_id=args.clusterId,
        geo_peers=args.geoPeers.split(",") if args.geoPeers else None,
        geo_rate_mbps=args.geoRateMBps,
        meta_log_dir=args.metaLogDir,
    )
    f.start()
    print(f"filer http={args.port} grpc={f.grpc_port}")
    _wait()


def cmd_mount(args) -> None:
    from .mount.fuse import FuseMount, available
    from .mount.wfs import WFS

    if not available():
        raise SystemExit(
            "mount: libfuse.so.2 or /dev/fuse unavailable on this host"
        )
    filer_http = args.filer
    wfs = WFS(
        filer_grpc=_grpc_addr(filer_http),
        filer_http=filer_http,
        chunk_size_mb=args.chunkSizeLimitMB,
        collection=args.collection,
        replication=args.replication,
        cache_dir=args.cacheDir or None,
        cache_mem_mb=args.cacheCapacityMB,
    )
    wfs.start_meta_subscription()
    m = FuseMount(wfs, args.dir, allow_other=args.allowOthers)
    m.start()
    print(f"mounted {filer_http} on {args.dir}")
    try:
        _wait()
    finally:
        m.stop()


def cmd_msg_broker(args) -> None:
    from .messaging.broker import MessageBrokerServer

    b = MessageBrokerServer(
        filer=args.filer,
        ip=args.ip,
        port=args.port,
        peers=args.peers.split(",") if args.peers else None,
    )
    b.start()
    print(f"message broker grpc={args.port} filer={args.filer}")
    _wait()


def cmd_filer_replicate(args) -> None:
    from .replication import FilerSource, Replicator
    from .replication.sink import FilerSink, LocalSink, S3Sink

    if args.sink:
        args.sink_type = args.sink_type or "local"
        if args.sink_type == "filer":
            sink = FilerSink(args.sink)
        elif args.sink_type == "s3":
            endpoint, _, bucket = args.sink.partition("/")
            sink = S3Sink(endpoint, bucket or "backup")
        else:
            sink = LocalSink(args.sink)
        label = f"{args.sink_type}:{args.sink}"
    else:  # no -sink flag: replication.toml picks it (scaffold.go model)
        from .replication.sink import sink_from_config
        from .util.config import load_configuration

        if args.sink_type:
            raise SystemExit(
                "-sink.type without -sink would be silently ignored; "
                "either give both flags or configure replication.toml")
        conf = load_configuration("replication", required=True)
        sink, label = sink_from_config(conf)
        # (explicit flags always win; toml fills only omitted ones)
        # [source.filer] wins over flag DEFAULTS in toml mode, so the
        # scaffolded source section is honored, not silently ignored
        if conf.get_bool("source.filer.enabled"):
            addr = conf.get_string("source.filer.grpcAddress", "")
            if addr and args.filer is None:
                from .replication.source import GRPC_PORT_OFFSET

                host, _, port_s = addr.partition(":")
                try:
                    port = int(port_s)
                    if port <= GRPC_PORT_OFFSET:
                        raise ValueError
                except ValueError:
                    raise SystemExit(
                        f"[source.filer] grpcAddress {addr!r} must be "
                        "host:port with the gRPC port (HTTP port + "
                        f"{GRPC_PORT_OFFSET})") from None
                args.filer = f"{host}:{port - GRPC_PORT_OFFSET}"
            if args.filerPath is None:
                args.filerPath = conf.get_string("source.filer.directory",
                                                 "/")
    src_filer = args.filer or "127.0.0.1:8888"
    src_path = args.filerPath or "/"
    rep = Replicator(FilerSource(src_filer), sink, src_path)
    print(f"replicating {src_filer}{src_path} -> {label}")
    rep.run()


def cmd_filer_backup(args) -> None:
    from .replication import FilerSource, LocalSink, Replicator

    rep = Replicator(FilerSource(args.filer), LocalSink(args.dir),
                     args.filerPath)
    print(f"backing up {args.filer}{args.filerPath} -> {args.dir}")
    rep.run()


def cmd_filer_meta_tail(args) -> None:
    from .replication.source import subscribe_metadata

    for resp in subscribe_metadata(args.filer, args.pathPrefix,
                                   client_name="meta.tail"):
        n = resp.event_notification
        kind = ("delete" if not n.new_entry.name
                else "create" if not n.old_entry.name else "update")
        name = n.new_entry.name or n.old_entry.name
        print(f"{resp.ts_ns} {kind} {resp.directory}/{name}")


def cmd_filer_meta_backup(args) -> None:
    """Continuously back up filer metadata into a local store
    (command/filer_meta_backup.go)."""
    from .replication.meta_backup import MetaBackup

    store, store_path, store_options = _filer_store_selection(args.store)
    mb = MetaBackup.with_store(args.filer, store, store_path,
                               filer_dir=args.filerDir, **store_options)
    mb.run(restart=args.restart)


def cmd_filer_sync(args) -> None:
    """Bidirectional sync between two filers.  Both directions share one
    sync signature: every replayed mutation carries it, and each side's
    subscription skips events so signed — writes cannot ping-pong
    (command/filer_sync.go)."""
    import random
    import threading

    from .replication import FilerSource, Replicator
    from .replication.sink import FilerSink

    a, b = args.a, args.b
    sig = random.randint(1, 2**31 - 1)
    ra = Replicator(FilerSource(a), FilerSink(b, signature=sig),
                    args.filerPath, signature=sig)
    rb = Replicator(FilerSource(b), FilerSink(a, signature=sig),
                    args.filerPath, signature=sig)
    ta = threading.Thread(target=ra.run, daemon=True)
    tb = threading.Thread(target=rb.run, daemon=True)
    ta.start()
    tb.start()
    print(f"filer.sync {a} <-> {b} prefix={args.filerPath}")
    _wait()


def cmd_s3(args) -> None:
    from .s3api.server import S3ApiServer

    s = S3ApiServer(
        filer=args.filer,
        port=args.port,
        config_path=args.config,
        domain=args.domainName,
        iam_config_filer_path=args.iam_config or "",
        masters=args.master or "",
        geo_masters=args.geoMaster or "",
    )
    s.start()
    print(f"s3 gateway http={args.port} "
          + (f"masters={args.master} (fleet discovery)" if args.master
             else f"filer={args.filer}"))
    _wait()


def cmd_iam(args) -> None:
    from .iamapi.server import IamApiServer

    s = IamApiServer(filer=args.filer, port=args.port)
    s.start()
    print(f"iam api http={args.port} filer={args.filer}")
    _wait()


def cmd_backup(args) -> None:
    from .tools.backup import backup_volume

    res = backup_volume(args.server, args.volumeId, args.dir,
                        collection=args.collection)
    print(f"volume {args.volumeId}: appended {res['appended']} needles"
          + (" (full resync)" if res["full_resync"] else ""))


def cmd_upload(args) -> None:
    import json as _json

    from .tools.backup import upload_files

    results = upload_files(args.master, args.files,
                           collection=args.collection,
                           replication=args.replication, ttl=args.ttl)
    print(_json.dumps(results, indent=2))


def cmd_download(args) -> None:
    from .tools.backup import download_files

    for path in download_files(args.server, args.fids, args.dir):
        print(path)


def cmd_filer_cat(args) -> None:
    import sys as _sys

    from .tools.backup import filer_cat

    _sys.stdout.buffer.write(filer_cat(args.filer, args.path))


def cmd_filer_copy(args) -> None:
    from .tools.backup import filer_copy

    for p in filer_copy(args.filer, args.sources, args.dest):
        print(p)


def cmd_gateway(args) -> None:
    from .gateway import GatewayServer

    g = GatewayServer(
        masters=args.master.split(","),
        filers=args.filer.split(",") if args.filer else None,
        port=args.port,
    )
    g.start()
    print(f"gateway http={args.port} masters={args.master}")
    _wait()


def cmd_webdav(args) -> None:
    from .webdav.server import WebDavServer

    s = WebDavServer(filer=args.filer, port=args.port)
    s.start()
    print(f"webdav http={args.port} filer={args.filer}")
    _wait()


def cmd_ftp(args) -> None:
    """FTP gateway over the filer (the reference's ftpd is an 81-LoC
    stub; this one serves)."""
    import json as _json

    from .ftpd.server import FtpServer

    users = {}
    if args.users:
        users = _json.loads(open(args.users).read())
    s = FtpServer(filer=args.filer, ip=args.ip, port=args.port, users=users)
    s.start()
    print(f"ftp on {args.ip}:{s.port} filer={args.filer}")
    _wait()


def cmd_shell(args) -> None:
    from .shell.commands import CommandEnv, run_command
    from .util.config import load_configuration

    master, filer = args.master, getattr(args, "filer", "")
    sconf = load_configuration("shell")
    if sconf.loaded:  # shell.toml fills only OMITTED flags (default=None)
        if master is None:
            master = sconf.get_string("cluster.default.master", "")
        if not filer:
            filer = sconf.get_string("cluster.default.filer", "")
    master = master or "127.0.0.1:9333"
    env = CommandEnv(_grpc_addr(master))
    if filer:
        env.option["filer"] = filer
    if args.command:
        print(run_command(env, args.command))
        return
    while True:
        try:
            line = input("> ")
        except EOFError:
            break
        if line.strip() in ("exit", "quit"):
            break
        try:
            print(run_command(env, line))
        except Exception as e:
            print(f"error: {e}")


def cmd_bench(args) -> None:
    from .tools.benchmark import run_benchmark

    run_benchmark(
        master=args.master,
        num_files=args.n,
        file_size=args.size,
        concurrency=args.c,
        do_read=not args.write_only,
    )


def cmd_fix(args) -> None:
    from .tools.offline import fix_index

    fix_index(args.dir, args.volumeId, args.collection)
    print(f"rebuilt index for volume {args.volumeId}")


def cmd_compact(args) -> None:
    from .storage.vacuum import vacuum_volume
    from .storage.volume import Volume

    v = Volume(args.dir, args.collection, args.volumeId)
    vacuum_volume(v)
    v.close()
    print(f"compacted volume {args.volumeId}")


def cmd_export(args) -> None:
    from .tools.offline import export_volume

    n = export_volume(args.dir, args.volumeId, args.collection, args.output)
    print(f"exported {n} needles to {args.output}")


def _load_tier_backends(path: str) -> dict | None:
    if not path:
        return None
    import json

    with open(path) as f:
        return json.load(f)


def _grpc_addr(master: str) -> str:
    """Convert a server's HTTP address to its gRPC address (+10000)."""
    host, port = master.rsplit(":", 1)
    return f"{host}:{int(port) + 10000}"


def _wait() -> None:
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass


def cmd_scaffold(args) -> None:
    import os

    from .util.scaffold import scaffold

    text = scaffold(args.config)
    if args.output == "-":
        print(text, end="")
    else:
        path = os.path.join(args.output, f"{args.config}.toml")
        with open(path, "w") as f:
            f.write(text)
        print(f"wrote {path}")


# cmd -> the certificate identity its gRPC LISTENER presents; commands
# absent here are pure clients. "server" hosts master+volume in one
# process behind one listener credential set.
_TLS_COMPONENT = {
    "master": "master", "volume": "volume", "filer": "filer",
    "server": "master", "msgBroker": "broker",
}


def _security_jwt_key() -> str:
    """security.toml [jwt.signing].key — the flagless way to arm write
    JWTs cluster-wide (scaffold.go's security template)."""
    from .util.config import load_configuration

    return load_configuration("security").get_string("jwt.signing.key")


def _security_white_list() -> list[str] | None:
    from .util.config import load_configuration

    wl = load_configuration("security").get_list("guard.white_list")
    return [str(ip) for ip in wl] or None


def _configure_security(cmd: str) -> None:
    """Load security.toml and install mTLS credentials for this process
    (reference: every command resolves LoadServerTLS/LoadClientTLS at
    boot from the shared security.toml)."""
    from .pb import rpc as rpclib
    from .security.tls import load_client_credentials, load_server_credentials
    from .util.config import load_configuration

    conf = load_configuration("security")
    if not conf.loaded:
        return
    component = _TLS_COMPONENT.get(cmd, "client")
    server_creds = (
        load_server_credentials(conf, component)
        if cmd in _TLS_COMPONENT else None
    )
    channel_creds = load_client_credentials(conf, component)
    if server_creds or channel_creds:
        rpclib.configure_security(server_creds, channel_creds)


def _setup_profiling(args) -> None:
    if getattr(args, "cpuprofile", "") or getattr(args, "memprofile", ""):
        from .util.grace import setup_profiling

        setup_profiling(args.cpuprofile, args.memprofile)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="seaweedfs_tpu")
    p.add_argument("-cpuprofile", default="",
                   help="write a cProfile dump here at exit")
    p.add_argument("-memprofile", default="",
                   help="write a tracemalloc top-allocations report here "
                        "at exit")
    sub = p.add_subparsers(dest="cmd", required=True)

    m = sub.add_parser("master")
    m.add_argument("-ip", default="127.0.0.1")
    m.add_argument("-port", type=int, default=9333)
    m.add_argument("-volumeSizeLimitMB", type=int, default=30 * 1024)
    m.add_argument("-defaultReplication", default="000")
    m.add_argument("-maintenanceInterval", type=float, default=None,
               help="seconds between maintenance runs; 0 disables "
                    "(default: master.toml periodic_seconds)")
    m.add_argument("-lifecycleInterval", type=float, default=0.0,
                   help="lifecycle controller cycle seconds; 0 = manual "
                        "only (volume.lifecycle -apply)")
    m.add_argument("-lifecycleDir", default="",
                   help="crash-safe lifecycle journal directory; empty "
                        "keeps jobs in memory only")
    m.add_argument("-lifecycleRateMBps", type=float, default=None,
                   help="cluster background-I/O budget shared by "
                        "lifecycle jobs and scrub (None = env "
                        "SEAWEEDFS_TPU_LIFECYCLE_RATE_MBPS, 0 = "
                        "unthrottled)")
    m.add_argument("-lifecyclePolicy", default="",
                   help="JSON policy file: {collection: {field: value}}")
    m.add_argument("-repairDeadlineS", type=float, default=None,
                   help="total-repair-time bound for dead-node mass "
                        "repair; when a -lifecycleRateMBps budget is "
                        "set, the pushed background rate is raised to "
                        "what the bound requires (without a budget "
                        "repair traffic is unthrottled, so the bound "
                        "needs no boost).  None = env "
                        "SEAWEEDFS_TPU_MASS_REPAIR_DEADLINE_S, 0 = "
                        "no bound")
    m.add_argument("-metricsPort", type=int, default=0)
    m.add_argument("-jwtKey", default="")
    m.add_argument("-peers", default="",
                   help="comma-separated master quorum ip:port list (raft)")
    m.add_argument("-raftDir", default=".",
                   help="directory for persisted raft state")
    m.add_argument("-peerClusters", default="",
                   help="comma-separated REMOTE-cluster master http "
                        "addresses for the /cluster/geo registry")
    m.add_argument("-sloInterval", type=float, default=15.0,
                   help="SLO engine evaluation tick seconds (burn-rate "
                        "rules over family-filtered federation scrapes); "
                        "0 = evaluate only when /cluster/alerts is read")
    m.add_argument("-sloSpecs", default="",
                   help="JSON file with a list of SLO spec objects "
                        "(replaces the default suite; see METRICS.md "
                        "'SLOs & alerts')")
    m.add_argument("-canaryInterval", type=float, default=0.0,
                   help="synthetic canary probe tick seconds (black-box "
                        "write/read/delete, EC degraded read, routed "
                        "metadata, geo sentinel); 0 disables")
    m.add_argument("-canaryS3", default="",
                   help="S3 gateway http address the metadata_rt canary "
                        "routes through (empty = probe a registered "
                        "filer directly)")
    m.add_argument("-alertWebhook", default="",
                   help="POST every alert state transition to this URL "
                        "as JSON (the log sink always runs)")
    m.add_argument("-debugDir", default="",
                   help="flight-recorder bundle directory: alerts "
                        "transitioning to firing (and cluster.debug "
                        "-capture) snapshot cluster debug bundles here "
                        "with bounded retention (empty = in-memory ring)")
    m.set_defaults(fn=cmd_master)

    v = sub.add_parser("volume")
    v.add_argument("-dir", default="./data")
    v.add_argument("-mserver", default="127.0.0.1:9333")
    v.add_argument("-ip", default="127.0.0.1")
    v.add_argument("-port", type=int, default=8080)
    v.add_argument("-dataCenter", default="")
    v.add_argument("-rack", default="")
    v.add_argument("-max", type=int, default=7)
    v.add_argument("-port.tcp", dest="tcpPort", type=int, default=0,
                   help="experimental raw-TCP needle data path (0=off)")
    v.add_argument("-index", default="memory",
                   choices=("memory", "disk"),
                   help="needle map kind: in-RAM compact map, or "
                        "disk-backed sorted file for RAM-constrained "
                        "servers")
    v.add_argument("-offset.5bytes", dest="offset5", action="store_true",
                   help="5-byte needle offsets: 8TB volumes instead of "
                        "32GB (index files are NOT compatible with the "
                        "default 4-byte layout)")
    v.add_argument("-ec.codec", dest="ec_codec", default="",
                   choices=["auto", "cpu", "tpu", "tpu_xor", "tpu_mxu"])
    v.add_argument("-metricsPort", type=int, default=0)
    v.add_argument("-jwtKey", default="")
    v.add_argument("-whiteList", default="")
    v.add_argument("-tierBackends", default="",
                   help="JSON file: {\"s3.default\": {\"endpoint\": ...}}")
    v.set_defaults(fn=cmd_volume)

    s = sub.add_parser("server")
    s.add_argument("-dir", default="./data")
    s.add_argument("-ip", default="127.0.0.1")
    s.add_argument("-masterPort", type=int, default=9333)
    s.add_argument("-port", type=int, default=8080)
    s.add_argument("-ec.codec", dest="ec_codec", default="")
    s.add_argument("-filer", action="store_true",
                   help="also start a filer")
    s.add_argument("-filer.port", dest="filerPort", type=int, default=8888)
    s.add_argument("-filer.store", dest="filerStore", default="./filer.db")
    s.add_argument("-s3", action="store_true",
                   help="also start an S3 gateway (implies -filer)")
    s.add_argument("-s3.port", dest="s3Port", type=int, default=8333)
    s.set_defaults(fn=cmd_server)

    f = sub.add_parser("filer")
    f.add_argument("-master", default="127.0.0.1:9333")
    f.add_argument("-ip", default="127.0.0.1")
    f.add_argument("-port", type=int, default=8888)
    f.add_argument("-store", default="./filer.db")
    f.add_argument("-maxMB", type=int, default=4)
    f.add_argument("-metricsPort", type=int, default=0)
    f.add_argument("-peers", default="",
                   help="comma-separated peer filer http addresses for "
                        "metadata federation")
    f.add_argument("-encryptVolumeData", dest="cipher",
                   action="store_true",
                   help="AES-GCM encrypt chunk data before it reaches "
                        "volume servers")
    f.add_argument("-clusterId", type=int, default=0,
                   help="geo replication: this cluster's nonzero id "
                        "(the LWW tiebreak; enables HLC stamping and "
                        "the /.geo/* surface)")
    f.add_argument("-geoPeers", default="",
                   help="comma-separated REMOTE-cluster filer http "
                        "addresses to replicate to (active-active; one "
                        "journaled link per address)")
    f.add_argument("-geoRateMBps", type=float, default=None,
                   help="per-link replication budget (None = env "
                        "SEAWEEDFS_TPU_GEO_RATE_MBPS, 0 = unthrottled)")
    f.add_argument("-metaLogDir", default="",
                   help="durable metadata event log dir (default: "
                        "<store path>.metalog for disk stores)")
    f.set_defaults(fn=cmd_filer)

    mnt = sub.add_parser("mount")
    mnt.add_argument("-filer", default="127.0.0.1:8888")
    mnt.add_argument("-dir", required=True)
    mnt.add_argument("-collection", default="")
    mnt.add_argument("-replication", default="")
    mnt.add_argument("-chunkSizeLimitMB", type=int, default=4)
    mnt.add_argument("-cacheDir", default="")
    mnt.add_argument("-cacheCapacityMB", type=int, default=32)
    mnt.add_argument("-allowOthers", action="store_true")
    mnt.set_defaults(fn=cmd_mount)

    mb = sub.add_parser("msgBroker")
    mb.add_argument("-filer", default="127.0.0.1:8888")
    mb.add_argument("-ip", default="127.0.0.1")
    mb.add_argument("-port", type=int, default=17777)
    mb.add_argument("-peers", default="",
                    help="comma-separated peer broker grpc addresses")
    mb.set_defaults(fn=cmd_msg_broker)

    fr = sub.add_parser("filer.replicate")
    fr.add_argument("-filer", default=None,
                    help="source filer ip:port (omitted -> "
                         "replication.toml, then 127.0.0.1:8888)")
    fr.add_argument("-filerPath", default=None,
                    help="source path (omitted -> replication.toml, "
                         "then /)")
    fr.add_argument("-sink.type", dest="sink_type", default="",
                    choices=["", "local", "filer", "s3"],
                    help="with -sink; defaults to local")
    fr.add_argument("-sink", default="",
                    help="local dir, target filer ip:port, or s3 "
                         "endpoint/bucket; empty = use replication.toml")
    fr.set_defaults(fn=cmd_filer_replicate)

    fb = sub.add_parser("filer.backup")
    fb.add_argument("-filer", default="127.0.0.1:8888")
    fb.add_argument("-filerPath", default="/")
    fb.add_argument("-dir", required=True)
    fb.set_defaults(fn=cmd_filer_backup)

    fmt = sub.add_parser("filer.meta.tail")
    fmt.add_argument("-filer", default="127.0.0.1:8888")
    fmt.add_argument("-pathPrefix", default="/")
    fmt.set_defaults(fn=cmd_filer_meta_tail)

    fmb = sub.add_parser("filer.meta.backup")
    fmb.add_argument("-filer", default="127.0.0.1:8888")
    fmb.add_argument("-filerDir", default="/",
                     help="only back up this folder of the filer")
    fmb.add_argument("-restart", action="store_true",
                     help="copy the full metadata before the async "
                          "incremental backup")
    fmb.add_argument("-store", default="./meta_backup.db",
                     help="backup sqlite db path")
    fmb.set_defaults(fn=cmd_filer_meta_backup)

    fsy = sub.add_parser("filer.sync")
    fsy.add_argument("-a", required=True, help="filer A ip:port")
    fsy.add_argument("-b", required=True, help="filer B ip:port")
    fsy.add_argument("-filerPath", default="/")
    fsy.set_defaults(fn=cmd_filer_sync)

    s3p = sub.add_parser("s3")
    s3p.add_argument("-filer", default="127.0.0.1:8888",
                     help="filer http address(es), comma-separated; a "
                          "list pins a static fleet ring")
    s3p.add_argument("-master", default="",
                     help="comma-separated master http addresses: "
                          "discover the filer fleet from the master's "
                          "registrations and route by consistent hash "
                          "(the stateless-gateway mode)")
    s3p.add_argument("-port", type=int, default=8333)
    s3p.add_argument("-config", default="",
                     help="s3 identities json (empty = auth disabled)")
    s3p.add_argument("-domainName", default="")
    s3p.add_argument("-iam.config", dest="iam_config",
                     default="/etc/iam/identity.json",
                     help="filer path of the IAM-managed identity json "
                          "('' disables the live-reload loop)")
    s3p.add_argument("-geoMaster", default="",
                     help="comma-separated REMOTE-cluster master http "
                          "addresses: when the local filer fleet is "
                          "entirely unreachable, reads/writes fail over "
                          "to the remote cluster (geo failover)")
    s3p.set_defaults(fn=cmd_s3)

    iamp = sub.add_parser("iam")
    iamp.add_argument("-filer", default="127.0.0.1:8888")
    iamp.add_argument("-port", type=int, default=8111)
    iamp.set_defaults(fn=cmd_iam)

    gwp = sub.add_parser("gateway")
    gwp.add_argument("-master", default="127.0.0.1:9333",
                     help="comma-separated master http addresses")
    gwp.add_argument("-filer", default="",
                     help="comma-separated filer http addresses")
    gwp.add_argument("-port", type=int, default=5647)
    gwp.set_defaults(fn=cmd_gateway)

    wd = sub.add_parser("webdav")
    wd.add_argument("-filer", default="127.0.0.1:8888")
    wd.add_argument("-port", type=int, default=7333)
    wd.set_defaults(fn=cmd_webdav)

    fp = sub.add_parser("ftp")
    fp.add_argument("-filer", default="127.0.0.1:8888")
    fp.add_argument("-ip", default="127.0.0.1")
    fp.add_argument("-port", type=int, default=8021)
    fp.add_argument("-users", default="",
                    help='JSON file {"user": "password"}; empty = anonymous')
    fp.set_defaults(fn=cmd_ftp)

    bk = sub.add_parser("backup")
    bk.add_argument("-server", default="127.0.0.1:9333",
                    help="master http address")
    bk.add_argument("-volumeId", type=int, required=True)
    bk.add_argument("-dir", default=".")
    bk.add_argument("-collection", default="")
    bk.set_defaults(fn=cmd_backup)

    up = sub.add_parser("upload")
    up.add_argument("-master", default="127.0.0.1:9333")
    up.add_argument("-collection", default="")
    up.add_argument("-replication", default="")
    up.add_argument("-ttl", default="")
    up.add_argument("files", nargs="+")
    up.set_defaults(fn=cmd_upload)

    dl = sub.add_parser("download")
    dl.add_argument("-server", default="127.0.0.1:9333",
                    help="master http address")
    dl.add_argument("-dir", default=".")
    dl.add_argument("fids", nargs="+")
    dl.set_defaults(fn=cmd_download)

    fcat = sub.add_parser("filer.cat")
    fcat.add_argument("-filer", default="127.0.0.1:8888")
    fcat.add_argument("path")
    fcat.set_defaults(fn=cmd_filer_cat)

    fcp = sub.add_parser("filer.copy")
    fcp.add_argument("-filer", default="127.0.0.1:8888")
    fcp.add_argument("sources", nargs="+")
    fcp.add_argument("dest")
    fcp.set_defaults(fn=cmd_filer_copy)

    sh = sub.add_parser("shell")
    sh.add_argument("-master", default=None,
                    help="master ip:port (omitted -> shell.toml, then "
                         "127.0.0.1:9333)")
    sh.add_argument("-filer", default="",
                    help="filer http address for fs.*/s3.* commands")
    sh.add_argument("-c", dest="command", default="")
    sh.set_defaults(fn=cmd_shell)

    b = sub.add_parser("benchmark")
    b.add_argument("-master", default="127.0.0.1:9333")
    b.add_argument("-n", type=int, default=1024)
    b.add_argument("-size", type=int, default=1024)
    b.add_argument("-c", type=int, default=16)
    b.add_argument("--write-only", action="store_true")
    b.set_defaults(fn=cmd_bench)

    fx = sub.add_parser("fix")
    fx.add_argument("-dir", default=".")
    fx.add_argument("-volumeId", type=int, required=True)
    fx.add_argument("-collection", default="")
    fx.set_defaults(fn=cmd_fix)

    cp = sub.add_parser("compact")
    cp.add_argument("-dir", default=".")
    cp.add_argument("-volumeId", type=int, required=True)
    cp.add_argument("-collection", default="")
    cp.set_defaults(fn=cmd_compact)

    ex = sub.add_parser("export")
    ex.add_argument("-dir", default=".")
    ex.add_argument("-volumeId", type=int, required=True)
    ex.add_argument("-collection", default="")
    ex.add_argument("-o", dest="output", default="export.tar")
    ex.set_defaults(fn=cmd_export)

    ver = sub.add_parser("version")
    ver.set_defaults(fn=lambda a: print("seaweedfs_tpu 0.1.0"))

    sc = sub.add_parser("scaffold")
    sc.add_argument("-config", default="security",
                    choices=("security", "master", "filer",
                             "notification", "replication", "shell"))
    sc.add_argument("-output", default=".",
                    help="output directory, or - for stdout")
    sc.set_defaults(fn=cmd_scaffold)

    args = p.parse_args(argv)
    _setup_profiling(args)
    if args.cmd != "scaffold":
        _configure_security(args.cmd)
    args.fn(args)


if __name__ == "__main__":
    main()
