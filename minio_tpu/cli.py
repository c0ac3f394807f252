"""CLI entry: `python -m minio_tpu server /data{1...4}` — behavioral
parity with the reference's cli app (main.go:34 → cmd.Main → `minio
server` command, cmd/main.go:90-167), argparse instead of minio/cli.
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="minio-tpu",
        description="TPU-native S3-compatible erasure-coded object storage",
    )
    sub = p.add_subparsers(dest="command", required=True)
    srv = sub.add_parser("server", help="start the object storage server")
    srv.add_argument(
        "endpoints", nargs="+",
        help="data dirs, with {1...N} ellipses for erasure pools "
             "(a single plain dir starts FS mode)",
    )
    srv.add_argument("--address", default="127.0.0.1")
    srv.add_argument("--port", type=int, default=9000)
    srv.add_argument("--fs", action="store_true", help="force FS mode")
    srv.add_argument(
        "--set-drive-count", type=int, default=None,
        help="drives per erasure set (default: auto by GCD)",
    )
    srv.add_argument(
        "--storage-address", default=None, metavar="HOST:PORT",
        help="this node's storage-plane address for multi-node "
             "topologies with http:// endpoints (peer plane binds "
             "PORT+1)",
    )
    srv.add_argument(
        "--certs-dir", default=None, metavar="DIR",
        help="directory holding public.crt + private.key; serves every "
             "plane (S3 + storage/lock/peer RPC) over TLS with hot cert "
             "reload (also via MTPU_CERTS_DIR)",
    )
    srv.add_argument("--quiet", action="store_true")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if args.command == "server":
        from .server import Server

        server = Server(
            args.endpoints, address=args.address, port=args.port,
            fs_mode=args.fs, set_drive_count=args.set_drive_count,
            storage_address=args.storage_address,
            certs_dir=args.certs_dir,
        ).start()
        if not args.quiet:
            scheme = "https" if server.cert_manager is not None else "http"
            print(f"minio-tpu {server.mode} mode")
            print(f"S3 endpoint: {scheme}://{server.endpoint}")
            print(f"RootUser: {server.root_user}")
            if server.engine_backend is not None:
                b = server.engine_backend
                print(f"Erasure backend: platform={b.platform} "
                      f"device_kind={b.device_kind!r} devices={b.count}")
        try:
            action = server.wait()
        finally:
            server.stop()
        if action == "restart":
            # In-place re-exec with the same argv (ref cmd/service.go
            # restartProcess).
            import os

            os.execv(sys.executable,
                     [sys.executable, "-m", "minio_tpu", *sys.argv[1:]])
        return 0
    return 1


if __name__ == "__main__":
    sys.exit(main())
