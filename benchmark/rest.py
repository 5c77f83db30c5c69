"""The one REST call the benchmark makes (no JAX: the load process uses it)."""

from __future__ import annotations

import json
import urllib.request


def post(port: int, route: str, payload: dict, timeout: float = 300.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}", json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())
