"""The cell's store and reference answers, kept across runs of a checkout.

The store holds the configuration's corpus, which its ``corpus.seed``
fixes, and is built through the program's own durable path under
``bench/.cache/<config>/store-<key>``.  The key covers the
configuration's corpus and store settings, the generator file it uses
(``bench/corpora/<corpus.generator>.py``, or ``bench/corpus.py`` where
it names none) and every source file of the program, so a change to any
of them builds anew; every other run only reopens the store with
``DynaWarpStore.open``, which is what a restart costs.

Reference answers depend on the lines and on the references alone:
they are kept per traffic and ``--seed`` under
``answers/<traffic>/<key>``, whose key covers the corpus settings, the
generator file, ``bench/reference.py``, ``bench/ops.py`` and the query
modules under ``bench/queries/`` that the traffic's pool names (the
references of the ops they bring).
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil

import numpy as np

from . import byname

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")


def _file_digest(path: str) -> bytes:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).digest()


def src_hash(src_dir: str = os.path.join(ROOT, "src")) -> str:
    """Hash of every ``.py`` file under the program's source tree."""
    h = hashlib.sha256()
    for base, dirs, files in os.walk(src_dir):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(base, name)
                h.update(os.path.relpath(path, src_dir).encode())
                h.update(_file_digest(path))
    return h.hexdigest()[:16]


def _key(settings: dict, files: list[str], extra: str = "") -> str:
    h = hashlib.sha256(json.dumps(settings, sort_keys=True).encode())
    for path in files:
        h.update(_file_digest(path))
    h.update(extra.encode())
    return h.hexdigest()[:16]


def generator_file(cfg: dict) -> str:
    """The file of the configuration's corpus generator."""
    name = cfg["corpus"].get("generator")
    return (byname.path("corpora", name) if name
            else os.path.join(BENCH_DIR, "corpus.py"))


def store_key(cfg: dict) -> str:
    return _key({"corpus": cfg["corpus"], "store": cfg["store"]},
                [generator_file(cfg)], src_hash())


def answers_key(cfg: dict, query_files: list[str] = ()) -> str:
    return _key({"corpus": cfg["corpus"]},
                [generator_file(cfg)] + [os.path.join(BENCH_DIR, f) for f in
                                         ("reference.py", "ops.py")]
                + sorted(query_files))


def _config_dir(config: str) -> str:
    return os.path.join(CACHE_DIR, config)


def _drop_others(cdir: str, prefix: str, keep: str) -> None:
    for name in os.listdir(cdir):
        if name.startswith(prefix) and name != keep:
            shutil.rmtree(os.path.join(cdir, name))


def store_path(config: str, cfg: dict, lines) -> str:
    """Path of the finished durable store of ``config``, built from
    ``lines`` (a callable) if no store of this key is there yet.  Stores
    of other keys are removed first."""
    from repro.logstore.store import DynaWarpStore
    cdir = _config_dir(config)
    name = f"store-{store_key(cfg)}"
    path = os.path.join(cdir, name)
    if os.path.exists(os.path.join(path, "built.json")):
        return path
    os.makedirs(cdir, exist_ok=True)
    _drop_others(cdir, "store-", "")
    tmp = path + ".building"
    store = DynaWarpStore(path=tmp, **cfg["store"])
    store.ingest(lines())
    store.finish()
    store.close()
    with open(os.path.join(tmp, "built.json"), "w") as f:
        json.dump({"config": config}, f)
    os.replace(tmp, path)
    return path


def stored_bytes(path: str) -> int:
    """Bytes of every file of the store, bookkeeping of this cache
    excepted."""
    return sum(os.path.getsize(os.path.join(path, f))
               for f in os.listdir(path) if f != "built.json")


def _answers_file(config: str, cfg: dict, traffic: str, seed: int,
                  query_files: list[str]) -> str:
    return os.path.join(_config_dir(config), "answers", traffic,
                        answers_key(cfg, query_files), f"seed-{seed}.npz")


def load_answers(config: str, cfg: dict, traffic: str, seed: int,
                 query_files: list[str]) -> dict:
    """Cached reference answers, ``{(op, text): ids}``."""
    path = _answers_file(config, cfg, traffic, seed, query_files)
    if not os.path.exists(path):
        return {}
    with np.load(path) as z:
        keys = json.loads(str(z["keys"]))
        offsets, ids = z["offsets"], z["ids"]
    return {tuple(k): ids[offsets[i]:offsets[i + 1]]
            for i, k in enumerate(keys)}


def save_answers(config: str, cfg: dict, traffic: str, seed: int,
                 query_files: list[str], answers: dict) -> None:
    keys = sorted(answers)
    sizes = [len(answers[k]) for k in keys]
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    ids = (np.concatenate([np.asarray(answers[k], np.int64) for k in keys])
           if keys else np.empty(0, np.int64))
    path = _answers_file(config, cfg, traffic, seed, query_files)
    adir = os.path.dirname(path)
    os.makedirs(adir, exist_ok=True)
    _drop_others(os.path.dirname(adir), "", os.path.basename(adir))
    np.savez(path + ".tmp.npz", keys=json.dumps([list(k) for k in keys]),
             offsets=offsets, ids=ids)
    os.replace(path + ".tmp.npz", path)
