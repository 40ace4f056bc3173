"""``"kind": "train_packed"``: global batches of packed documents."""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np

from perfbench.traffic import lognormal, tokens


def batches(job: dict, seed: int, vocab: int, seq_length: int,
            ) -> Iterator[Dict[str, np.ndarray]]:
    """An endless stream of global batches of ``sequences_per_step`` rows of
    `seq_length` tokens, documents of log-normal length packed end to end (a
    document may continue in the next row). Rows carry what
    ``pretrain_gpt``'s ``batch_iter`` takes: tokens, labels, loss_mask (0
    where the label is another document's first token), position_ids and
    segment_ids."""
    rows = job["sequences_per_step"]
    scale = seq_length / job["seq_length"]
    shape = np.random.default_rng(job["shape_seed"])
    doc_lens = lognormal(shape, job["pool_documents"], job["doc_len"], scale)
    rng = np.random.default_rng(seed)

    def documents():
        doc_id = 0
        while True:
            for n in map(int, doc_lens):
                yield tokens(rng, n, vocab), np.full(n, doc_id, np.int64)
                doc_id += 1

    docs = documents()
    need = rows * seq_length + 1
    tok_buf = np.zeros(0, np.int32)
    doc_buf = np.zeros(0, np.int64)
    while True:
        while len(tok_buf) < need:
            t, d = next(docs)
            tok_buf = np.concatenate([tok_buf, t])
            doc_buf = np.concatenate([doc_buf, d])
        batch = {k: np.empty((rows, seq_length), np.int32)
                 for k in ("tokens", "labels", "position_ids", "segment_ids")}
        batch["loss_mask"] = np.empty((rows, seq_length), np.float32)
        for r in range(rows):
            lo = r * seq_length
            tok = tok_buf[lo:lo + seq_length + 1]
            doc = doc_buf[lo:lo + seq_length + 1]
            seg = doc[:-1] - doc[0]
            start = np.concatenate([[True], seg[1:] != seg[:-1]])
            idx = np.arange(seq_length)
            batch["tokens"][r] = tok[:-1]
            batch["labels"][r] = tok[1:]
            batch["segment_ids"][r] = seg
            batch["position_ids"][r] = idx - np.maximum.accumulate(
                np.where(start, idx, 0))
            batch["loss_mask"][r] = doc[1:] == doc[:-1]
        tok_buf = tok_buf[rows * seq_length:]
        doc_buf = doc_buf[rows * seq_length:]
        yield batch
