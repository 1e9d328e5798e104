"""Beside a benchmark run on the same machine: finds the engine pod among the
listening local ports (the harness prints no address and dumps no scrape),
and keeps in one JSON file the newest `/metrics` lines that start with the
given prefixes and the named keys of `/debug/engine`'s perf section. Never
imports jax: the chip stays the engine's.

    python3 benchmarks/poll_engine.py out.json kubeai_engine_attn_pairs chunk_kernel_hit_share,chunk_kernel_tiles &
"""

import json
import sys
import time
import urllib.request


def listening_ports() -> set[int]:
    found = set()
    for path in ("/proc/net/tcp", "/proc/net/tcp6"):
        try:
            rows = open(path).read().splitlines()[1:]
        except OSError:
            continue
        found.update(int(f[1].rsplit(":", 1)[1], 16) for f in (row.split() for row in rows) if f[3] == "0A")
    return found


def get(port: int, path: str) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=2) as reply:
        return reply.read().decode()


def main() -> None:
    out, prefixes, perf_keys = sys.argv[1], tuple(sys.argv[2].split(",")), sys.argv[3].split(",")
    while True:
        for port in listening_ports():
            try:
                body = get(port, "/metrics")
            except Exception:
                continue
            if "kubeai_engine_" not in body:
                continue
            try:
                perf = json.loads(get(port, "/debug/engine?limit=1"))["perf"]
                perf = {key: perf.get(key) for key in perf_keys}
            except Exception as e:
                perf = {"error": str(e)}
            metrics = [l for l in body.splitlines() if l.startswith(prefixes)]
            if not metrics and "error" in perf:
                continue  # the operator's own port: it names the engine's series and has no perf section
            with open(out, "w") as f:
                json.dump({"at": time.time(), "port": port, "metrics": metrics, "perf": perf}, f)
        time.sleep(5)


if __name__ == "__main__":
    main()
