"""HTTP reads of the operator and the engine pod, and the Prometheus text
parser the counters go through. The benchmark's own copy: no import from
the program, no jax."""

from __future__ import annotations

import http.client
import json
import re
import time

_SAMPLE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{.*\})?\s+(\S+)")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


class Unreachable(Exception):
    pass


def http_get(base: str, path: str, timeout: float = 30) -> str:
    host, port = base.split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read().decode(errors="replace")
        if resp.status != 200:
            raise Unreachable(f"GET {base}{path}: {resp.status} {body[:300]}")
        return body
    except (OSError, http.client.HTTPException) as e:
        raise Unreachable(f"GET {base}{path}: {type(e).__name__}: {e}") from e
    finally:
        conn.close()


def http_get_or_none(base: str, path: str) -> str:
    try:
        return http_get(base, path, timeout=5)
    except Unreachable:
        return ""


def parse_prometheus(text: str) -> dict[str, list[tuple[dict, float]]]:
    """name -> [(labels, value)]. Exemplar suffixes (` # {...}`) dropped."""
    out: dict[str, list[tuple[dict, float]]] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        line = line.split(" # ", 1)[0]
        m = _SAMPLE.match(line)
        if not m:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        labels = dict(_LABEL.findall(m.group(2) or ""))
        out.setdefault(m.group(1), []).append((labels, value))
    return out


class Scrape:
    """One /metrics read of the engine, with the host time it was taken."""

    def __init__(self, text: str, at: float):
        self.at = at
        self.series = parse_prometheus(text)

    def value(self, name: str, **labels) -> float:
        return sum(
            v for have, v in self.series.get(name, ())
            if all(have.get(k) == w for k, w in labels.items())
        )

    def has(self, name: str) -> bool:
        return name in self.series


def scrape(engine: str) -> Scrape:
    return Scrape(http_get(engine, "/metrics"), time.monotonic())


def engine_address(base: str, model: str) -> str:
    """host:port of the one engine pod, as the operator's router sees it."""
    endpoints = json.loads(http_get(base, "/debug/endpoints"))["models"].get(model, [])
    if len(endpoints) != 1:
        raise Unreachable(f"expected one engine endpoint for {model}, found {endpoints}")
    return endpoints[0]["address"]
