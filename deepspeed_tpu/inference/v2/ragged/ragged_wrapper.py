"""Ragged batch descriptor: host-side assembly of the padded device batch.

Counterpart of reference ``inference/v2/ragged/ragged_wrapper.py``
(``RaggedBatchWrapper`` :267 — token concatenation + inflight descriptors
uploaded via the pinned fast_host_buffer). The TPU program wants *static*
shapes, so the wrapper pads to (max_seqs, max_chunk) and carries per-seq
metadata arrays; XLA masks do the ragged part. One wrapper instance is
reused across steps (buffers re-filled, no allocation per step).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


class RaggedBatchWrapper:
    def __init__(self, max_seqs: int, max_chunk: int, max_blocks_per_seq: int,
                 min_chunk: int = 1, groups: int = 1):
        # K/V by layer group (ragged/manager.py): one block table a group,
        # ``block_tables`` [groups, S, MB]; one group keeps [S, MB]
        self.groups = int(groups)
        self.max_seqs = max_seqs
        self.max_chunk = max_chunk
        # the narrowest bucket a chunk wider than one token is padded to
        # (a model whose mixer works in tiles gains nothing from a
        # narrower program of its own); a one-token step stays [S, 1]
        self.min_chunk = min(max(int(min_chunk), 1), max_chunk)
        self.max_blocks_per_seq = max_blocks_per_seq
        self.clear()

    def clear(self):
        ms, mc, mb = self.max_seqs, self.max_chunk, self.max_blocks_per_seq
        self.tokens = np.zeros((ms, mc), np.int32)
        self.start_pos = np.zeros((ms,), np.int32)     # tokens already cached
        self.n_tokens = np.zeros((ms,), np.int32)      # new tokens this step
        self.block_tables = np.full(
            (ms, mb) if self.groups == 1 else (self.groups, ms, mb), -1,
            np.int32)
        self.uids: List[int] = []

    @property
    def current_sequences(self) -> int:
        return len(self.uids)

    @property
    def current_tokens(self) -> int:
        return int(self.n_tokens.sum())

    def insert_sequence(self, uid: int, tokens: Sequence[int], start_pos: int,
                        kv_blocks: Sequence[int]) -> int:
        """Add one sequence's chunk; returns its row index. ``kv_blocks``:
        its block table, or with several layer groups one table a group
        (equally long; an int32 array ``[groups, blocks]`` is copied as
        it is — ``DSStateManager.table_rows``)."""
        i = len(self.uids)
        if i >= self.max_seqs:
            raise ValueError("ragged batch full (max_seqs)")
        n = len(tokens)
        if n > self.max_chunk:
            raise ValueError(f"chunk {n} > max_chunk {self.max_chunk}")
        tables = np.asarray(kv_blocks, np.int32)
        if tables.shape[-1] > self.max_blocks_per_seq:
            raise ValueError("sequence exceeds max_blocks_per_seq")
        self.tokens[i, :n] = np.asarray(tokens, np.int32)
        self.start_pos[i] = start_pos
        self.n_tokens[i] = n
        # [blocks] into row i, or [groups, blocks] into every group's row i
        self.block_tables[..., i, :tables.shape[-1]] = tables
        self.uids.append(uid)
        return i

    def buckets(self) -> Tuple[List[int], List[int]]:
        """Every sequence count and every chunk width ``finalize`` can
        hand out (what an engine that compiles ahead has to cover)."""
        def pow2(cap):
            return sorted({self._bucket(1 << i, cap)
                           for i in range(cap.bit_length() + 1)})

        chunks = {c if c == 1 else max(c, self.min_chunk)
                  for c in pow2(self.max_chunk)}
        return pow2(self.max_seqs), sorted(chunks)

    @staticmethod
    def _bucket(n: int, cap: int) -> int:
        """Smallest power of two >= n, capped. Bounds the number of compiled
        program variants to O(log² cap) while letting a decode step run a
        [S, 1] batch instead of the full [max_seqs, max_chunk] pad."""
        b = 1
        while b < n:
            b *= 2
        return min(b, cap)

    def bucket(self, n_seqs: int, widest: int) -> Tuple[int, int]:
        """The ``[S, C]`` that ``finalize`` trims ``n_seqs`` rows to, the
        widest of them ``widest`` tokens."""
        S = self._bucket(max(n_seqs, 1), self.max_seqs)
        C = self._bucket(max(widest, 1), self.max_chunk)
        return S, (max(C, self.min_chunk) if C > 1 else C)

    def finalize(self, bucketed: bool = True) -> Dict[str, np.ndarray]:
        """Device-ready arrays (the reference's pinned-buffer upload).

        With ``bucketed`` (default), the batch is trimmed to
        (bucket(num_seqs), bucket(max chunk width)) — rows beyond the real
        sequences carry n_tokens=0 / table=-1 and are fully masked."""
        if not bucketed:
            return {
                "tokens": self.tokens,
                "start_pos": self.start_pos,
                "n_tokens": self.n_tokens,
                "block_tables": self.block_tables,
            }
        S, C = self.bucket(len(self.uids), int(self.n_tokens.max()))
        return {
            "tokens": self.tokens[:S, :C],
            "start_pos": self.start_pos[:S],
            "n_tokens": self.n_tokens[:S],
            "block_tables": self.block_tables[..., :S, :],
        }

    def finalize_merged(self, positions: int) -> Dict[str, np.ndarray]:
        """The same arrays for a batch whose row 0 is its one row wider
        than one token, with the positions laid end to end
        (``PagedCausalLM._forward``): ``tokens`` [1, positions] holds row
        0's chunk padded to ``positions - S`` places, then one place for
        each of the ``S`` rows the batch is bucketed to -- a one-token
        row's token; row 0's own place there stays a pad."""
        arrays = self.finalize()
        chunk = positions - len(arrays["start_pos"])
        tokens = np.concatenate([self.tokens[0, :chunk],
                                 self.tokens[:positions - chunk, 0]])
        tokens[chunk] = 0
        return dict(arrays, tokens=tokens[None])
