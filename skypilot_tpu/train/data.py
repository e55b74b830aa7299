"""Tokenized-dataset input pipeline: binary token shards → train batches.

The reference leaves input pipelines to the frameworks it launches (torch
DataLoader / tf.data inside MaxText — SURVEY §2.9); here the pipeline is
in-tree with a native hot path: `native/dataloader.cpp` mmaps the shards
and a C++ prefetch thread assembles batches into a ring buffer (no GIL),
so the step loop only memcpys. When no compiler is available the
`TokenDataset` falls back to a numpy implementation with identical
semantics (same windows, same host-sharding, same affine shuffle walk) —
the logmux pattern (native/logmux.py).

Shard format ("SKYTOK1"): 8-byte magic, u32 version, u32 dtype code
(2 = uint16, 4 = uint32), u64 token count, then the tokens. Write with
`write_token_shard`; tokenize with whatever you like.

Host sharding: windows are dealt round-robin (window_index % num_hosts ==
host_rank), so multi-host jobs see disjoint data with zero coordination —
the loader needs only the rank/world values the agent already exports
(agent/constants.py env contract).
"""
from __future__ import annotations

import ctypes
import glob
import logging
import math
import os
import struct
import subprocess
import threading
from typing import Iterator, List, Optional, Sequence

import numpy as np

logger = logging.getLogger(__name__)

MAGIC = b'SKYTOK1\x00'
_HEADER = struct.Struct('<8sIIQ')  # magic, version, dtype_code, count

_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'native')
_SO_PATH = os.path.join(_SRC_DIR, 'libdataloader.so')
_BUILD_LOCK = threading.Lock()
_lib = None
_load_failed = False


def write_token_shard(path: str, tokens: np.ndarray) -> None:
    """Write a token shard. uint16 when the vocab allows (half the disk
    and read bandwidth), uint32 otherwise."""
    tokens = np.asarray(tokens)
    if tokens.ndim != 1:
        raise ValueError('tokens must be 1-D')
    if tokens.dtype not in (np.uint16, np.uint32):
        if tokens.min(initial=0) < 0:
            raise ValueError('tokens must be non-negative')
        dtype = np.uint16 if (tokens.size == 0 or
                              tokens.max(initial=0) < 2**16) else np.uint32
        tokens = tokens.astype(dtype)
    code = 2 if tokens.dtype == np.uint16 else 4
    tmp = f'{path}.tmp-{os.getpid()}'
    with open(tmp, 'wb') as f:
        f.write(_HEADER.pack(MAGIC, 1, code, tokens.size))
        f.write(tokens.tobytes())
    os.replace(tmp, path)


def read_token_shard(path: str) -> np.ndarray:
    with open(path, 'rb') as f:
        magic, version, code, count = _HEADER.unpack(
            f.read(_HEADER.size))
        if magic != MAGIC or version != 1 or code not in (2, 4):
            raise ValueError(f'bad token shard: {path}')
        dtype = np.uint16 if code == 2 else np.uint32
        data = np.frombuffer(f.read(count * code), dtype=dtype)
        if data.size != count:
            raise ValueError(f'truncated token shard: {path}')
        return data


def _load_native() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None:
        return _lib
    if _load_failed:
        return None
    with _BUILD_LOCK:
        if _lib is not None:
            return _lib
        src = os.path.join(_SRC_DIR, 'dataloader.cpp')
        needs_build = (not os.path.exists(_SO_PATH) or
                       (os.path.exists(src) and
                        os.path.getmtime(src) > os.path.getmtime(_SO_PATH)))
        if needs_build:
            cmd = ['g++', '-O2', '-shared', '-fPIC', '-std=c++17', '-o',
                   _SO_PATH, src, '-lpthread']
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True,
                                      timeout=120, check=False)
            except (OSError, subprocess.TimeoutExpired) as e:
                logger.warning('dataloader build skipped (numpy '
                               'loader in use): %s', e)
                _load_failed = True
                return None
            if proc.returncode != 0:
                logger.warning('dataloader build failed:\n%s', proc.stderr)
                _load_failed = True
                return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
            lib.dl_open.restype = ctypes.c_void_p
            lib.dl_open.argtypes = [
                ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
                ctypes.c_int, ctypes.c_int, ctypes.c_longlong,
                ctypes.c_longlong, ctypes.c_ulonglong,
                ctypes.c_longlong, ctypes.c_char_p, ctypes.c_int]
            lib.dl_next.restype = ctypes.c_int
            lib.dl_next.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_uint32)]
            lib.dl_num_windows.restype = ctypes.c_longlong
            lib.dl_num_windows.argtypes = [ctypes.c_void_p]
            lib.dl_close.argtypes = [ctypes.c_void_p]
        except OSError as e:
            logger.warning('dataloader load failed: %s', e)
            _load_failed = True
            return None
        _lib = lib
        return _lib


def _gcd_walk_params(seed: int, n: int):
    a = (seed % n) | 1
    while math.gcd(a, n) != 1:
        a = a + 2 if (a + 2) % n else 1
    return (a or 1), (seed // 3) % n


class TokenDataset:
    """Infinite iterator of train batches from token shards.

    Yields dicts {'inputs', 'targets', 'mask'} of shape (batch, seq) —
    exactly what make_train_step consumes. Deterministic for a given
    (paths, seed, host_rank); `start_batch` fast-forwards the stream so a
    checkpoint-resumed run continues with the batches the interrupted run
    would have seen next (train/run.py passes the restored step).
    """

    def __init__(self,
                 paths: Sequence[str],
                 batch_size: int,
                 seq_len: int,
                 host_rank: int = 0,
                 num_hosts: int = 1,
                 seed: int = 0,
                 start_batch: int = 0,
                 prefer_native: bool = True):
        if isinstance(paths, str):
            paths = sorted(glob.glob(os.path.join(paths, '*.bin')))
        if not paths:
            raise ValueError('no token shards found')
        self.paths: List[str] = list(paths)
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.host_rank = host_rank
        self.num_hosts = num_hosts
        self.seed = seed
        self.start_batch = start_batch
        self._handle = None
        self._lib = _load_native() if prefer_native else None
        self.native = False
        if self._lib is not None:
            err = ctypes.create_string_buffer(256)
            c_paths = (ctypes.c_char_p * len(self.paths))(
                *[p.encode() for p in self.paths])
            handle = self._lib.dl_open(
                c_paths, len(self.paths), batch_size, seq_len,
                host_rank, num_hosts, seed, start_batch, err, 256)
            if handle:
                self._handle = ctypes.c_void_p(handle)
                self.native = True
            else:
                raise ValueError(
                    f'dataloader: {err.value.decode() or "open failed"}')
        if not self.native:
            self._init_fallback()

    # -- fallback (numpy) ------------------------------------------------
    def _init_fallback(self) -> None:
        self._shards = [read_token_shard(p) for p in self.paths]
        window = self.seq_len + 1
        self._windows_per_shard = [
            (s.size - 1) // self.seq_len if s.size >= window else 0
            for s in self._shards]
        total = sum(self._windows_per_shard)
        mine = ((total - 1 - self.host_rank) // self.num_hosts + 1
                if total > self.host_rank else 0)
        if mine < self.batch_size:
            raise ValueError(
                'not enough data: fewer windows than batch size')
        self._my_windows = mine
        self._mul, self._add = _gcd_walk_params(self.seed, mine)
        self._cursor = self.start_batch
        self._firsts = np.cumsum([0] + self._windows_per_shard[:-1])

    def _fallback_window(self, w: int) -> np.ndarray:
        i = int(np.searchsorted(self._firsts, w, side='right') - 1)
        local = w - int(self._firsts[i])
        start = local * self.seq_len
        return self._shards[i][start:start + self.seq_len + 1].astype(
            np.uint32)

    # -- public ----------------------------------------------------------
    @property
    def num_windows(self) -> int:
        if self.native:
            return int(self._lib.dl_num_windows(self._handle))
        return self._my_windows

    def __iter__(self) -> Iterator[dict]:
        while True:
            yield self.next_batch()

    def next_batch(self) -> dict:
        window = self.seq_len + 1
        if self.native:
            out = np.empty((self.batch_size, window), np.uint32)
            rc = self._lib.dl_next(
                self._handle,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
            if rc < 0:
                raise RuntimeError('dataloader closed')
        else:
            batch_count = self._my_windows // self.batch_size
            b = self._cursor
            self._cursor += 1
            epoch, k0 = divmod(b, batch_count)
            out = np.empty((self.batch_size, window), np.uint32)
            for i in range(self.batch_size):
                k = k0 * self.batch_size + i
                j = (self._mul * k + self._add +
                     epoch * 7919) % self._my_windows
                w = self.host_rank + j * self.num_hosts
                out[i] = self._fallback_window(w)
        tokens = out.astype(np.int32)
        return {
            'inputs': tokens[:, :-1],
            'targets': tokens[:, 1:],
            'mask': np.ones((self.batch_size, self.seq_len), np.int32),
        }

    def close(self) -> None:
        if self.native and self._handle is not None:
            self._lib.dl_close(self._handle)
            self._handle = None
            self.native = False

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.close()
        except Exception:  # pylint: disable=broad-except
            pass


class SftJsonlDataset:
    """Supervised fine-tune batches with prompt-masked loss.

    Input: a JSONL file of pre-tokenized examples, one object per line:
        {"prompt": [token ids...], "completion": [token ids...]}
    Each batch row is prompt+completion (truncated to seq+1, right-padded
    with `pad_id`); `mask` is 1 exactly on completion-token targets, so
    the trainer's masked cross-entropy (trainer.py: loss uses
    batch['mask']) never trains on prompt or padding — the torchtune-SFT
    semantics of the reference's llm/llama-3_1-finetuning recipe, in-tree.

    Host-sharding and ordering follow TokenDataset: examples dealt
    round-robin to hosts, affine-walk shuffle per epoch, `start_batch`
    fast-forwards for checkpoint resume.
    """

    def __init__(self,
                 path: str,
                 batch_size: int,
                 seq_len: int,
                 host_rank: int = 0,
                 num_hosts: int = 1,
                 seed: int = 0,
                 start_batch: int = 0,
                 pad_id: int = 0):
        import json as json_lib
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.pad_id = pad_id
        examples = []
        with open(path, encoding='utf-8') as f:
            for i, line in enumerate(f):
                line = line.strip()
                if not line:
                    continue
                if i % num_hosts != host_rank:
                    continue
                obj = json_lib.loads(line)
                prompt = list(obj['prompt'])
                completion = list(obj['completion'])
                if not completion:
                    raise ValueError(f'{path}:{i + 1}: empty completion')
                examples.append((prompt, completion))
        if len(examples) < batch_size:
            raise ValueError('not enough data: fewer examples than '
                             'batch size')
        self._examples = examples
        n = len(examples)
        self._mul, self._add = _gcd_walk_params(seed, n)
        self._cursor = start_batch

    @property
    def num_examples(self) -> int:
        return len(self._examples)

    def _row(self, ex) -> tuple:
        prompt, completion = ex
        window = self.seq_len + 1
        tokens = (prompt + completion)[:window]
        prompt_len = min(len(prompt), len(tokens))
        n_tok = len(tokens)
        row = np.full(window, self.pad_id, np.int32)
        row[:n_tok] = tokens
        # Target position p predicts token p+1: train exactly where that
        # token is a completion token.
        mask = np.zeros(self.seq_len, np.int32)
        mask[max(prompt_len - 1, 0):n_tok - 1] = 1
        return row, mask

    def next_batch(self) -> dict:
        n = len(self._examples)
        batch_count = n // self.batch_size
        b = self._cursor
        self._cursor += 1
        epoch, k0 = divmod(b, batch_count)
        rows = np.empty((self.batch_size, self.seq_len + 1), np.int32)
        masks = np.empty((self.batch_size, self.seq_len), np.int32)
        for i in range(self.batch_size):
            k = k0 * self.batch_size + i
            j = (self._mul * k + self._add + epoch * 7919) % n
            rows[i], masks[i] = self._row(self._examples[j])
        return {
            'inputs': rows[:, :-1],
            'targets': rows[:, 1:],
            'mask': masks,
        }

    def __iter__(self) -> Iterator[dict]:
        while True:
            yield self.next_batch()

    def close(self) -> None:
        pass
