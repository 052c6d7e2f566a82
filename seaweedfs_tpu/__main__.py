"""Top-level CLI dispatcher — the `weed` binary analog.

Mirrors weed/weed.go + weed/command/command.go (SURVEY.md §2 "CLI
dispatcher"): a table of subcommands, each owning its flags:

    python -m seaweedfs_tpu master -port 9333                control plane
    python -m seaweedfs_tpu volume -dir d -mserver host:port data plane
    python -m seaweedfs_tpu shell  -dir ... | -master ...    admin shell
    python -m seaweedfs_tpu scaffold -config security        config template
"""

from __future__ import annotations

import sys


def _run_shell(argv: list[str]) -> int:
    from .shell.cli import main
    return main(argv)


def _run_master(argv: list[str]) -> int:
    from .cluster.master import main
    return main(argv)


def _run_volume(argv: list[str]) -> int:
    from .cluster.volume_server import main
    return main(argv)


def _run_scaffold(argv: list[str]) -> int:
    import argparse

    from .util import config
    p = argparse.ArgumentParser(prog="scaffold")
    p.add_argument("-config", required=True)
    args = p.parse_args(argv)
    print(config.scaffold(args.config), end="")
    return 0


def _run_cluster(argv: list[str]) -> int:
    from .cluster_launcher import main
    return main(argv)


def _run_tls_gen(argv: list[str]) -> int:
    import argparse

    from .util import tls
    p = argparse.ArgumentParser(
        prog="tls.gen",
        description="self-signed CA + cluster pair for [grpc.tls]")
    p.add_argument("-dir", required=True)
    p.add_argument("-hosts", default="localhost",
                   help="comma-separated DNS SANs")
    p.add_argument("-ips", default="127.0.0.1",
                   help="comma-separated IP SANs")
    args = p.parse_args(argv)
    paths = tls.generate_cluster_credentials(
        args.dir,
        hosts=tuple(h for h in args.hosts.split(",") if h),
        ips=tuple(i for i in args.ips.split(",") if i))
    for k in ("ca", "cert", "key"):
        print(f"{k} = \"{paths[k]}\"")
    return 0


def _run_filer(argv: list[str]) -> int:
    from .cluster.filer_server import main
    return main(argv)


def _run_upload(argv: list[str]) -> int:
    from .cli_tools import run_upload
    return run_upload(argv)


def _run_download(argv: list[str]) -> int:
    from .cli_tools import run_download
    return run_download(argv)


def _run_delete(argv: list[str]) -> int:
    from .cli_tools import run_delete
    return run_delete(argv)


def _run_benchmark(argv: list[str]) -> int:
    from .cli_tools import run_benchmark
    return run_benchmark(argv)


def _run_s3(argv: list[str]) -> int:
    from .gateway.s3 import main
    return main(argv)


def _run_mount(argv: list[str]) -> int:
    from .mount.cli import main
    return main(argv)


def _run_filer_replicate(argv: list[str]) -> int:
    from .replication.replicator import main
    return main(argv)


def _run_filer_sync(argv: list[str]) -> int:
    from .replication.filer_sync import main
    return main(argv)


def _run_filer_meta_backup(argv: list[str]) -> int:
    from .replication.meta_backup import main
    return main(argv)


def _run_filer_copy(argv: list[str]) -> int:
    from .cli_tools import run_filer_copy
    return run_filer_copy(argv)


def _run_fix(argv: list[str]) -> int:
    from .volume_tools import run_fix
    return run_fix(argv)


def _run_backup(argv: list[str]) -> int:
    from .volume_tools import run_backup
    return run_backup(argv)


def _run_server(argv: list[str]) -> int:
    from .server_cmd import main
    return main(argv)


def _run_compact(argv: list[str]) -> int:
    from .server_cmd import run_compact
    return run_compact(argv)


def _run_export(argv: list[str]) -> int:
    from .volume_tools import run_export
    return run_export(argv)


def _run_watch(argv: list[str]) -> int:
    from .volume_tools import run_watch
    return run_watch(argv)


def _run_webdav(argv: list[str]) -> int:
    from .gateway.webdav import main
    return main(argv)


def _run_version(argv: list[str]) -> int:
    import platform

    import jax

    from . import __version__
    head = (f"seaweedfs-tpu {__version__} "
            f"(python {platform.python_version()}, jax {jax.__version__}")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"{head}): backend failed to start: {e}")
        return 1
    print(f"{head}, {len(devices)} x {devices[0].device_kind} "
          f"[{devices[0].platform}])")
    return 0


COMMANDS = {
    "shell": _run_shell,
    "master": _run_master,
    "volume": _run_volume,
    "filer": _run_filer,
    "upload": _run_upload,
    "download": _run_download,
    "delete": _run_delete,
    "benchmark": _run_benchmark,
    "s3": _run_s3,
    "webdav": _run_webdav,
    "mount": _run_mount,
    "filer.replicate": _run_filer_replicate,
    "filer.sync": _run_filer_sync,
    "filer.meta.backup": _run_filer_meta_backup,
    "filer.copy": _run_filer_copy,
    "fix": _run_fix,
    "backup": _run_backup,
    "export": _run_export,
    "server": _run_server,
    "watch": _run_watch,
    "compact": _run_compact,
    "scaffold": _run_scaffold,
    "tls.gen": _run_tls_gen,
    "cluster": _run_cluster,
    "version": _run_version,
}


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0] in ("-h", "--help", "help"):
        print("usage: python -m seaweedfs_tpu <command> [flags]\n\n"
              "commands:\n  " + "\n  ".join(sorted(COMMANDS)),
              file=sys.stderr)
        return 0 if argv else 1
    name = argv[0]
    fn = COMMANDS.get(name)
    if fn is None:
        print(f"unknown command {name!r}", file=sys.stderr)
        return 1
    return fn(argv[1:])


if __name__ == "__main__":
    sys.exit(main())
