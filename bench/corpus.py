"""LogHub-style corpus generator, vectorised.

A copy of the statistical model of ``repro.logstore.datasets`` (COPR
§5, Table 2), kept here so that later changes to the program cannot move
the yardstick:

  * lines per source follow Zipf(``zipf_a``) over ``n_sources`` sources,
    and lines arrive sorted by source (partitioned ingest);
  * every source speaks 2 to 5 of the 20 templates;
  * variable slots draw from per-source value pools of
    ``values_per_source`` IPs, 16-letter ids and hex ids, with a fresh
    value in 2% of the slots; numbers, ports and users are drawn anew.

The program's generator draws line by line (one Python loop per line);
this one draws each slot of each template for all its lines at once, so
the same distribution costs a few seconds for a million lines.  The two
give different lines for one seed: the statistics match, not the text
(``bench/tests/test_bench_corpus.py`` compares them).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

TEMPLATES = [
    "INFO dfs.DataNode$PacketResponder: PacketResponder {num} for block blk_{id} terminating",
    "INFO dfs.FSNamesystem: BLOCK* NameSystem.addStoredBlock: blockMap updated: {ip}:{port} is added to blk_{id} size {num}",
    "WARN dfs.DataNode: Slow BlockReceiver write packet to mirror took {num}ms (threshold=300ms)",
    "INFO spark.executor.Executor: Finished task {num}.0 in stage {num}.0 (TID {num}). {num} bytes result sent to driver",
    "INFO spark.storage.BlockManager: Found block rdd_{num}_{num} locally",
    "ERROR spark.scheduler.TaskSetManager: Task {num} in stage {num}.0 failed {num} times; aborting job",
    "INFO sshd[{num}]: Accepted publickey for {user} from {ip} port {port} ssh2: RSA SHA256:{hex}",
    "INFO sshd[{num}]: Connection closed by {ip} port {port} [preauth]",
    "WARN sshd[{num}]: Failed password for invalid user {user} from {ip} port {port} ssh2",
    "INFO kubelet: Successfully pulled image \"registry.local/{user}/{id}:v{num}\" in {num}ms",
    "ERROR kubelet: Pod \"{id}\" failed to start: container {hex} exited with code {num}",
    "INFO nginx: {ip} - - GET /api/v{num}/users/{id} HTTP/1.1 200 {num}",
    "INFO nginx: {ip} - - POST /api/v{num}/sessions HTTP/1.1 401 {num}",
    "INFO app.RequestHandler: request_id={id} user={user} latency_ms={num} status=OK",
    "WARN app.RetryPolicy: retrying request_id={id} attempt={num} backoff_ms={num}",
    "ERROR app.Db: connection to {ip}:{port} lost: timeout after {num}ms (pool={user})",
    "INFO gc: pause {num}ms heap {num}M->{num}M",
    "DEBUG cache.LRU: evicted key={hex} size={num}B age={num}s",
    "INFO auth.TokenService: issued token {hex} for tenant {user} ttl={num}s",
    "WARN quota.Limiter: tenant {user} exceeded {num} req/s, throttling request_id={id}",
]

USERS = ["alice", "bob", "carol", "dave", "erin", "frank", "grace", "heidi",
         "ivan", "judy", "mallory", "oscar", "peggy", "trent", "victor",
         "walter", "svc-ingest", "svc-query", "svc-batch", "root"]

FRESH_SHARE = 0.02
_LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)
_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)


@dataclass
class Corpus:
    """What a generator gives: every generator under ``bench/corpora/``
    returns one.  Scenarios read ``lines`` and ``sources``."""
    lines: list[str]
    sources: np.ndarray        # (N,) int32 source of each line
    templates: np.ndarray | None = None    # (N,) int32 template of each line

    @property
    def n_lines(self) -> int:
        return len(self.lines)

    def raw_bytes(self) -> int:
        """UTF-8 bytes of the lines, one newline each."""
        return sum(len(s.encode()) for s in self.lines) + self.n_lines


def _split(template: str) -> tuple[list[str], list[str]]:
    """Literal parts and slot kinds: ``parts[0] slot[0] parts[1] ...``."""
    parts, slots, rest = [], [], template
    while "{" in rest:
        head, tail = rest.split("{", 1)
        kind, rest = tail.split("}", 1)
        parts.append(head)
        slots.append(kind)
    parts.append(rest)
    return parts, slots


def _strings(codes: np.ndarray, alphabet: np.ndarray) -> list[str]:
    """Rows of alphabet indices -> strings."""
    width = codes.shape[-1]
    raw = np.ascontiguousarray(alphabet[codes]).view(f"S{width}").ravel()
    return raw.astype(f"U{width}").tolist()


def _ips(rng, n: int) -> list[str]:
    octets = rng.integers(1, 255, size=(n, 4)).astype(str)
    return [".".join(row) for row in octets.tolist()]


def random_ids(rng, n: int, width: int = 16) -> list[str]:
    return _strings(rng.integers(0, 26, size=(n, width)), _LETTERS)


def _hexes(rng, n: int, width: int = 12) -> list[str]:
    return _strings(rng.integers(0, 16, size=(n, width)), _HEX)


_FRESH = {"ip": _ips, "id": random_ids, "hex": _hexes}


def generate(*, n_lines: int, n_sources: int, seed: int,
             zipf_a: float = 1.4, values_per_source: int = 40) -> Corpus:
    """``n_lines`` lines from ``seed``; the same arguments give the same
    lines."""
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, n_sources + 1) ** zipf_a
    w /= w.sum()
    sources = np.sort(rng.choice(n_sources, size=n_lines, p=w))
    n_tpl = rng.integers(2, 6, size=n_sources)
    tpl_of = np.zeros((n_sources, 5), np.int64)
    for s in range(n_sources):
        tpl_of[s, :n_tpl[s]] = rng.choice(len(TEMPLATES), size=n_tpl[s],
                                          replace=False)
    pools = {kind: [make(rng, values_per_source) for _ in range(n_sources)]
             for kind, make in _FRESH.items()}
    pick = (rng.random(n_lines) * n_tpl[sources]).astype(np.int64)
    templates = tpl_of[sources, pick]

    lines: list = [None] * n_lines
    for t, template in enumerate(TEMPLATES):
        idx = np.flatnonzero(templates == t)
        if idx.size == 0:
            continue
        parts, slots = _split(template)
        out = [parts[0]] * idx.size
        for kind, lit in zip(slots, parts[1:]):
            vals = _slot_values(rng, kind, sources[idx], pools,
                                values_per_source)
            out = [a + v + lit for a, v in zip(out, vals)]
        for i, line in zip(idx.tolist(), out):
            lines[i] = line
    return Corpus(lines=lines, sources=sources.astype(np.int32),
                  templates=templates.astype(np.int32))


def _slot_values(rng, kind: str, src: np.ndarray, pools: dict,
                 pool_size: int) -> list[str]:
    m = src.size
    if kind == "num":
        return rng.integers(0, 100000, size=m).astype(str).tolist()
    if kind == "port":
        return rng.integers(1024, 65535, size=m).astype(str).tolist()
    if kind == "user":
        return [USERS[u] for u in rng.integers(len(USERS), size=m).tolist()]
    j = rng.integers(pool_size, size=m)
    pool = pools[kind]
    vals = [pool[s][k] for s, k in zip(src.tolist(), j.tolist())]
    fresh = np.flatnonzero(rng.random(m) < FRESH_SHARE)
    for i, v in zip(fresh.tolist(), _FRESH[kind](rng, fresh.size)):
        vals[i] = v
    return vals
