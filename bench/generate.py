"""Traffic for the federated ZO cells, made from a seed on the host.

These are copies of the program's own generators (``data/synthetic.py``,
``data/partition.py``, ``data/corpus.py``), kept here so that a change to
the program cannot move the yardstick.  The program receives only the
arrays they return.

* the classification-LM task: each class has its own topic tokens, every
  sequence ends in a SEP token, and the loss reads the class's
  verbaliser logit (token id = class id) at the SEP position;
* the client partitions: Dirichlet(alpha) per class, and single-label;
* the pre-training batches the sensitivity mask is calibrated on.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np


@dataclass(frozen=True)
class Task:
    vocab: int
    seq_len: int
    n_classes: int = 4
    topic_tokens: int = 24
    noise: float = 0.25
    seed: int = 0

    @property
    def sep_token(self) -> int:
        return self.vocab - 1


def class_vocab(task: Task) -> np.ndarray:
    """[n_classes, topic_tokens] disjoint topic-token sets (no verbaliser,
    no SEP)."""
    rng = np.random.default_rng(task.seed)
    pool = rng.permutation(np.arange(task.n_classes, task.vocab - 1))
    need = task.n_classes * task.topic_tokens
    if need > len(pool):
        raise ValueError("vocabulary too small for the topic sets")
    return pool[:need].reshape(task.n_classes, task.topic_tokens)


def sample_dataset(task: Task, n: int, seed: int) -> Dict[str, np.ndarray]:
    """n labelled sequences: {'tokens': [n, S] int32, 'label': [n] int32}."""
    rng = np.random.default_rng(seed)
    cv = class_vocab(task)
    p = np.full(task.n_classes, 1.0 / task.n_classes)
    labels = rng.choice(task.n_classes, size=n, p=p)
    S = task.seq_len
    toks = np.empty((n, S), np.int32)
    body = S - 1
    for i, c in enumerate(labels):
        topic = rng.choice(cv[c], size=body)
        common = rng.integers(task.n_classes, task.vocab - 1, size=body)
        use_common = rng.random(body) < task.noise
        toks[i, :body] = np.where(use_common, common, topic)
        toks[i, body] = task.sep_token
    return {"tokens": toks, "label": labels.astype(np.int32)}


def pretrain_batches(task: Task, n_batches: int, batch_size: int,
                     seed: int) -> List[Dict[str, np.ndarray]]:
    """LM batches mixing every topic with common tokens, for the mask."""
    rng = np.random.default_rng(seed)
    cv = class_vocab(task)
    out = []
    for _ in range(n_batches):
        toks = np.empty((batch_size, task.seq_len), np.int32)
        for i in range(batch_size):
            c = rng.integers(task.n_classes)
            topic = rng.choice(cv[c], size=task.seq_len)
            common = rng.integers(0, task.vocab, size=task.seq_len)
            use_common = rng.random(task.seq_len) < 0.5
            toks[i] = np.where(use_common, common, topic)
        out.append({"tokens": toks})
    return out


def dirichlet_partition(labels: np.ndarray, n_clients: int, alpha: float,
                        seed: int, min_size: int = 2) -> List[np.ndarray]:
    """Class-wise Dirichlet split (the paper's Non-IID protocol)."""
    rng = np.random.default_rng(seed)
    n_classes = int(labels.max()) + 1
    while True:
        buckets: List[List[int]] = [[] for _ in range(n_clients)]
        for c in range(n_classes):
            idx = np.where(labels == c)[0]
            rng.shuffle(idx)
            props = rng.dirichlet([alpha] * n_clients)
            cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
            for b, part in zip(buckets, np.split(idx, cuts)):
                b.extend(part.tolist())
        if min(len(b) for b in buckets) >= min_size:
            return [np.sort(np.asarray(b)) for b in buckets]


def single_label_partition(labels: np.ndarray, n_clients: int,
                           seed: int) -> List[np.ndarray]:
    """Extreme Non-IID: each client holds one class (round robin)."""
    rng = np.random.default_rng(seed)
    n_classes = int(labels.max()) + 1
    out = []
    for k in range(n_clients):
        idx = np.where(labels == k % n_classes)[0]
        sub = rng.choice(idx, size=max(2, len(idx) // max(
            1, n_clients // n_classes)), replace=False)
        out.append(np.sort(sub))
    return out


def mixed_partition(labels: np.ndarray, n_clients: int, alpha: float,
                    dirichlet_share: float, seed: int) -> List[np.ndarray]:
    """``dirichlet_share`` of the clients Dirichlet(alpha), the rest
    single-label (the paper's mix of mild and extreme Non-IID clients)."""
    nb = max(1, int(n_clients * dirichlet_share))
    return (dirichlet_partition(labels, nb, alpha, seed)
            + single_label_partition(labels, n_clients - nb, seed + 1))
