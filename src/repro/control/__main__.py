"""CLI: run or talk to the campaign control plane.

    # daemon (one per site)
    python -m repro.control serve --root /var/run/campaigns --fleet fleet.toml

    # clients
    python -m repro.control submit --url http://127.0.0.1:8765 campaign.toml
    python -m repro.control status --url http://127.0.0.1:8765
    python -m repro.control pause  --url http://127.0.0.1:8765 <id>
    python -m repro.control resume --url http://127.0.0.1:8765 <id>

The fleet file declares the site's shared slot budget::

    [pools.default]
    size = 8
    [pools.gpu]
    size = 2

``--port 0`` (the default) binds an ephemeral port; ``--port-file``
writes the bound port for whoever spawned the daemon (the CI smoke job
and the benchmark use this handshake).
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import threading
import tomllib
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional


def _load_fleet(path: str) -> Dict[str, int]:
    with open(path, "rb") as f:
        d = tomllib.load(f)
    pools = d.get("pools", d)  # accept both [pools.X] and top-level tables
    fleet: Dict[str, int] = {}
    for name, v in pools.items():
        if isinstance(v, dict):
            fleet[name] = int(v.get("size", 1))
        elif isinstance(v, int) and not isinstance(v, bool):
            fleet[name] = v
    if not fleet:
        raise ValueError(f"{path} declares no pools")
    return fleet


def _http(method: str, url: str, data: Optional[bytes] = None) -> Dict[str, Any]:
    req = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        req.add_header("Content-Type", "application/toml")
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return json.loads(resp.read().decode("utf-8"))
    except urllib.error.HTTPError as exc:
        body = exc.read().decode("utf-8", "replace")
        raise SystemExit(f"error: HTTP {exc.code} from {url}: {body.strip()}") from exc


def _cmd_serve(args: argparse.Namespace) -> int:
    from .api import ControlServer
    from .plane import ControlPlane

    fleet = _load_fleet(args.fleet)
    plane = ControlPlane(args.root, fleet, tick_s=args.tick).start()
    server = ControlServer(plane, host=args.host, port=args.port).start()
    if args.port_file:
        with open(args.port_file, "w") as f:
            f.write(str(server.port))
    print(f"control plane: root={args.root} fleet={fleet} url={server.url}", flush=True)

    stop = threading.Event()

    def _on_signal(signum: int, frame: Any) -> None:
        stop.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    while not stop.is_set():
        stop.wait(0.5)
    server.stop()
    plane.stop()
    return 0


def _cmd_submit(args: argparse.Namespace) -> int:
    with open(args.path) as f:
        body = f.read()
    url = f"{args.url.rstrip('/')}/campaigns"
    if args.name:
        url += f"?name={args.name}"
    rec = _http("POST", url, body.encode("utf-8"))
    print(json.dumps(rec, indent=2, sort_keys=True))
    return 0


def _cmd_status(args: argparse.Namespace) -> int:
    out = _http("GET", f"{args.url.rstrip('/')}/campaigns")
    print(json.dumps(out, indent=2, sort_keys=True))
    return 0


def _cmd_pause(args: argparse.Namespace) -> int:
    rec = _http("POST", f"{args.url.rstrip('/')}/campaigns/{args.id}/pause")
    print(json.dumps(rec, indent=2, sort_keys=True))
    return 0


def _cmd_resume(args: argparse.Namespace) -> int:
    rec = _http("POST", f"{args.url.rstrip('/')}/campaigns/{args.id}/resume")
    print(json.dumps(rec, indent=2, sort_keys=True))
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.control",
        description="Persistent multi-campaign control plane (daemon + clients).",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    serve = sub.add_parser("serve", help="run the control-plane daemon")
    serve.add_argument("--root", required=True, help="durable state directory")
    serve.add_argument("--fleet", required=True, help="fleet TOML ({pools.X: size})")
    serve.add_argument("--host", default="127.0.0.1")
    serve.add_argument("--port", type=int, default=0, help="0 = ephemeral")
    serve.add_argument("--port-file", default=None,
                       help="write the bound port here (spawn handshake)")
    serve.add_argument("--tick", type=float, default=0.5, help="scheduler tick seconds")
    serve.set_defaults(fn=_cmd_serve)

    submit = sub.add_parser("submit", help="submit a campaign TOML")
    submit.add_argument("path")
    submit.add_argument("--url", required=True)
    submit.add_argument("--name", default=None)
    submit.set_defaults(fn=_cmd_submit)

    status = sub.add_parser("status", help="list campaigns")
    status.add_argument("--url", required=True)
    status.set_defaults(fn=_cmd_status)

    pause = sub.add_parser("pause", help="pause a campaign (checkpoint + release)")
    pause.add_argument("id")
    pause.add_argument("--url", required=True)
    pause.set_defaults(fn=_cmd_pause)

    resume = sub.add_parser("resume", help="resume a paused campaign")
    resume.add_argument("id")
    resume.add_argument("--url", required=True)
    resume.set_defaults(fn=_cmd_resume)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
