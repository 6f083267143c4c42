"""Vectorized BM25 (Okapi) retrieval (paper Eq. 1-5).

The corpus (server or tool descriptions) is compiled once into a dense
IDF-weighted term matrix W [n_docs, vocab] such that scoring a query reduces
to a (sparse-query) matmul:

    score(q, d) = sum_{t in q} IDF(t) * tf(t,d)*(k1+1) / (tf(t,d) + k1*norm_d)
                = W[d] @ qcount

This makes stage-1 (server-level, Eq. 1-2) and stage-2 (tool-level, Eq. 3-4)
retrieval MXU-friendly; `repro.kernels.bm25_score` provides the tiled Pallas
kernel and this module is its oracle.

Softmax normalization of tool scores (Eq. 5) lives here as `softmax_expertise`.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

_TOKEN_RE = re.compile(r"[a-z0-9]+")

K1: float = 1.5
B: float = 0.75


def tokenize(text: str) -> list[str]:
    return _TOKEN_RE.findall(text.lower())


@dataclasses.dataclass
class Bm25Corpus:
    """Compiled corpus: vocabulary + IDF-weighted TF matrix."""

    vocab: dict  # token -> id
    weights: np.ndarray  # [n_docs, vocab] float32, W in the docstring
    n_docs: int

    def encode_query(self, text: str) -> np.ndarray:
        """Query -> term-count vector [vocab] (OOV terms are dropped, which
        matches BM25 semantics: unseen terms contribute zero)."""
        q = np.zeros((len(self.vocab),), dtype=np.float32)
        for tok in tokenize(text):
            j = self.vocab.get(tok)
            if j is not None:
                q[j] += 1.0
        return q

    def encode_queries(self, texts: Sequence[str]) -> np.ndarray:
        return np.stack([self.encode_query(t) for t in texts], axis=0)


def build_corpus(docs: Sequence[str], k1: float = K1, b: float = B) -> Bm25Corpus:
    """Compile documents into a Bm25Corpus (numpy; called once per pool)."""
    tokenized = [tokenize(d) for d in docs]
    vocab: dict = {}
    for toks in tokenized:
        for t in toks:
            if t not in vocab:
                vocab[t] = len(vocab)
    n_docs, n_vocab = len(docs), max(len(vocab), 1)

    tf = np.zeros((n_docs, n_vocab), dtype=np.float32)
    for i, toks in enumerate(tokenized):
        for t in toks:
            tf[i, vocab[t]] += 1.0

    doc_len = tf.sum(axis=1)
    avg_len = max(doc_len.mean(), 1e-6)
    df = (tf > 0).sum(axis=0).astype(np.float32)
    idf = np.log((n_docs - df + 0.5) / (df + 0.5) + 1.0)

    norm = k1 * (1.0 - b + b * doc_len / avg_len)  # [n_docs]
    weights = idf[None, :] * tf * (k1 + 1.0) / (tf + norm[:, None])
    weights = np.where(tf > 0, weights, 0.0).astype(np.float32)
    return Bm25Corpus(vocab=vocab, weights=weights, n_docs=n_docs)


def build_corpus_tiled(
    docs: Sequence[str], counts: Sequence[int], k1: float = K1, b: float = B
) -> Bm25Corpus:
    """Compile a *template-tiled* corpus: one weight row per template doc,
    with corpus statistics (IDF, average length, ``n_docs``) computed as if
    template ``i`` were replicated ``counts[i]`` times.

    Scoring a query against row ``i`` therefore equals scoring it against
    any of the ``counts[i]`` identical expanded documents — which is what
    lets mega-fleet indexes (`core.mesh_routing.TiledFleetIndex`) route
    10^5-10^6 identical-replica servers from a template-sized matmul.

    Parameters
    ----------
    docs : Sequence[str]
        The distinct template documents.
    counts : Sequence[int]
        Multiplicity of each template in the expanded corpus.
    """
    tokenized = [tokenize(d) for d in docs]
    vocab: dict = {}
    for toks in tokenized:
        for t in toks:
            if t not in vocab:
                vocab[t] = len(vocab)
    counts = np.asarray(counts, np.float64)
    n_docs = float(counts.sum())
    n_vocab = max(len(vocab), 1)

    tf = np.zeros((len(docs), n_vocab), dtype=np.float32)
    for i, toks in enumerate(tokenized):
        for t in toks:
            tf[i, vocab[t]] += 1.0

    doc_len = tf.sum(axis=1)
    avg_len = max(float((doc_len * counts).sum() / max(n_docs, 1.0)), 1e-6)
    df = ((tf > 0) * counts[:, None]).sum(axis=0).astype(np.float32)
    idf = np.log((n_docs - df + 0.5) / (df + 0.5) + 1.0)

    norm = k1 * (1.0 - b + b * doc_len / avg_len)
    weights = idf[None, :] * tf * (k1 + 1.0) / (tf + norm[:, None])
    weights = np.where(tf > 0, weights, 0.0).astype(np.float32)
    return Bm25Corpus(vocab=vocab, weights=weights, n_docs=int(n_docs))


# Every routing matmul has f32 operands.  A TPU's default f32 matmul is a
# single bf16 pass, which rounds the corpus weights and can flip an argmax
# against the f32 scalar reference; HIGHEST keeps them exact (CPU ignores it).
HIGHEST = jax.lax.Precision.HIGHEST

# Corpus rows scored by one matmul of `bm25_scores(..., row_blocks=True)`.
ROW_BLOCK = 128


def bm25_scores(weights: jnp.ndarray, qcounts: jnp.ndarray,
                row_blocks: bool = False) -> jnp.ndarray:
    """Score queries against the corpus: [n_docs, V] x [n_q, V] -> [n_q, n_docs].

    Pure-jnp oracle for kernels/bm25_score.  Query term *counts* saturate via
    the standard query-side BM25 (count clipped at 1 works for short queries;
    we keep raw counts to match rank-bm25 behaviour for repeated terms).

    With ``row_blocks`` the corpus is scored ``ROW_BLOCK`` rows at a time,
    every block by the same [n_q, V] x [V, ROW_BLOCK] matmul (the last block
    ends at the last row; a corpus of fewer rows is zero-padded to one
    block), so a row's score does not depend on how many rows are scored
    beside it: a template-tiled index and its densified expansion score
    bit-identically.  One matmul over all rows does not promise that, as
    XLA picks its accumulation order by the operand shapes (on the CPU, 30
    and 120 rows of the same weights scored 1 ulp apart); it is the
    default, being several times faster on a multi-core CPU.
    """
    q = qcounts.astype(jnp.float32)
    w = weights.astype(jnp.float32)
    if not row_blocks:
        return jnp.matmul(q, w.T, precision=HIGHEST)
    n = w.shape[0]
    if n < ROW_BLOCK:
        w = jnp.pad(w, ((0, ROW_BLOCK - n), (0, 0)))
    rows = max(n, ROW_BLOCK)
    n_blocks = -(-rows // ROW_BLOCK)
    starts = np.minimum(np.arange(n_blocks) * ROW_BLOCK, rows - ROW_BLOCK)
    s = jax.lax.map(
        lambda lo: jnp.matmul(
            q, jax.lax.dynamic_slice_in_dim(w, lo, ROW_BLOCK, 0).T,
            precision=HIGHEST,
        ),
        jnp.asarray(starts, jnp.int32),
    )                                                  # [n_blocks, n_q, B]
    r = np.arange(n)
    block = np.minimum(r // ROW_BLOCK, n_blocks - 1)
    return s[block, :, r - starts[block]].T


def topk(scores: jnp.ndarray, k: int):
    """Top-k along the last axis -> (values, indices), ties broken by index."""
    k = min(k, scores.shape[-1])
    return jax.lax.top_k(scores, k)


def softmax_expertise(scores: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Eq. 5: softmax normalization of BM25 scores into expertise C(i)."""
    return jax.nn.softmax(scores, axis=axis)
