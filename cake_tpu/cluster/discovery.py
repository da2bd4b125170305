"""Zero-config cluster discovery over UDP broadcast.

Protocol (same shape as ref: cake-core/src/cake/sharding/discovery.rs —
magic-tagged JSON query filtered by a SHA-256(cluster_key) prefix, unicast
JSON reply with device capabilities; ref lines 13-16, 75-84, 370-495):

  master -> broadcast:  {"magic": "CTPU", "hash": <8-hex>, "q": "discover"}
  worker -> unicast:    {"magic": "CTPU", "hash": ..., "name": ...,
                         "port": <service port>, "caps": {...}}

Capability detection is TPU-first: chip kind -> (TFLOPS, HBM) table via
jax.devices(), CPU fallback from /proc/meminfo (ref detect_gpus:91-162
does the same with nvidia-smi / sysctl).
"""
from __future__ import annotations

import json
import os
import socket
import threading
import time

from .auth import cluster_hash

DISCOVERY_PORT = 18337
MAGIC = "CTPU"
MAX_DATAGRAM = 4096

# chip kind -> (bf16 TFLOPS, HBM bytes) — public spec numbers (v5e: 197
# bf16; 394 is its int8 figure). Layer assignment is TFLOPS-proportional,
# so a kind that is not here is an error, never a guessed peak.
TPU_SPECS = {
    "TPU v2": (46.0, 8 << 30),
    "TPU v3": (123.0, 16 << 30),
    "TPU v4": (275.0, 32 << 30),
    "TPU v5 lite": (197.0, 16 << 30),
    "TPU v5e": (197.0, 16 << 30),
    "TPU v5p": (459.0, 95 << 30),
    "TPU v6 lite": (918.0, 32 << 30),
    "TPU v6e": (918.0, 32 << 30),
}


def detect_capabilities() -> dict:
    """Report backend/devices/memory/tflops for this host. The CPU row is
    for a host where JAX reports no TPU backend; a TPU that fails to
    initialize raises (a broken chip is not advertised as a CPU), and so
    does a TPU kind TPU_SPECS does not list."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return {"backend": "cpu", "device": "cpu", "n_devices": 1,
                "memory_bytes": _host_memory_bytes(), "tflops": 1.0}
    kind = devs[0].device_kind
    for prefix, (tf, hbm) in TPU_SPECS.items():
        if kind.startswith(prefix):
            return {"backend": "tpu", "device": kind, "n_devices": len(devs),
                    "memory_bytes": hbm * len(devs),
                    "tflops": tf * len(devs)}
    raise ValueError(f"unknown TPU kind {kind!r}: add its bf16 peak and HBM "
                     "size to cluster/discovery.TPU_SPECS")


def _host_memory_bytes() -> int:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 8 << 30


def get_broadcast_addresses() -> list[str]:
    """Interface-directed broadcast addresses + limited broadcast + loopback
    (ref: get_broadcast_addresses:499-592). Parsed from /proc/net/route +
    per-interface ioctl-free heuristics; always includes the fallbacks."""
    addrs = {"255.255.255.255", "127.0.0.1"}
    try:
        import subprocess
        out = subprocess.run(["ip", "-json", "addr"], capture_output=True,
                             timeout=2, text=True)
        if out.returncode == 0:
            for iface in json.loads(out.stdout):
                for a in iface.get("addr_info", []):
                    if a.get("family") == "inet" and a.get("broadcast"):
                        addrs.add(a["broadcast"])
    except Exception:
        pass
    return sorted(addrs)


class WorkerAdvertiser:
    """Background UDP listener answering discovery queries
    (ref: advertise_worker:429-495)."""

    def __init__(self, name: str, cluster_key: str, service_port: int,
                 discovery_port: int = DISCOVERY_PORT, caps: dict | None = None):
        self.name = name
        self.hash = cluster_hash(cluster_key)
        self.service_port = service_port
        self.discovery_port = discovery_port
        self.caps = caps or detect_capabilities()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._sock: socket.socket | None = None

    def start(self):
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        try:
            self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
        except OSError:
            pass
        self._sock.bind(("0.0.0.0", self.discovery_port))
        self._sock.settimeout(0.5)
        self._thread = threading.Thread(target=self._serve, daemon=True,
                                        name=f"advertiser-{self.name}")
        self._thread.start()
        return self

    def _serve(self):
        while not self._stop.is_set():
            try:
                data, addr = self._sock.recvfrom(MAX_DATAGRAM)
            except socket.timeout:
                continue
            except OSError:
                break
            try:
                msg = json.loads(data)
            except ValueError:
                continue
            if msg.get("magic") != MAGIC or msg.get("hash") != self.hash \
                    or msg.get("q") != "discover":
                continue
            reply = {"magic": MAGIC, "hash": self.hash, "name": self.name,
                     "port": self.service_port, "caps": self.caps,
                     "hostname": socket.gethostname(), "os": os.uname().sysname}
            try:
                self._sock.sendto(json.dumps(reply).encode(), addr)
            except OSError:
                pass

    def stop(self):
        self._stop.set()
        if self._sock:
            self._sock.close()
        if self._thread:
            self._thread.join(timeout=2)


def discover_workers(cluster_key: str, timeout: float = 2.0,
                     discovery_port: int = DISCOVERY_PORT,
                     expected: int | None = None) -> list[dict]:
    """Broadcast a query and collect worker replies
    (ref: discover_workers:604+). Returns a list of reply dicts with the
    sender ip added as "host"."""
    h = cluster_hash(cluster_key)
    query = json.dumps({"magic": MAGIC, "hash": h, "q": "discover"}).encode()
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_BROADCAST, 1)
    sock.settimeout(0.25)
    found: dict[tuple, dict] = {}
    baddrs = get_broadcast_addresses()      # once: spawns an `ip` subprocess
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for baddr in baddrs:
            try:
                sock.sendto(query, (baddr, discovery_port))
            except OSError:
                continue
        while True:
            try:
                data, addr = sock.recvfrom(MAX_DATAGRAM)
            except socket.timeout:
                break
            except OSError:
                break
            try:
                msg = json.loads(data)
            except ValueError:
                continue
            if msg.get("magic") != MAGIC or msg.get("hash") != h \
                    or "name" not in msg:
                continue
            msg["host"] = addr[0]
            found[(msg["name"],)] = msg
        if expected is not None and len(found) >= expected:
            break
    sock.close()
    return list(found.values())
