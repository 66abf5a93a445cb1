"""Node embeddings from graph-table walks: node2vec -> skip-gram.

The GNN training loop the reference's graph table feeds (PGL-style:
common_graph_table.cc serves walks to an embedding trainer): sample
node2vec walks from paddle_tpu's GraphTable, build (center, context)
skip-gram pairs with negative sampling, and train an nn.Embedding with
Adam until same-community nodes embed closer than cross-community ones.

Graph: two ring communities bridged by one edge — the classic sanity
structure where walk-based embeddings must separate the halves.

Run: python examples/graph_embedding.py [--epochs 60]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed.ps import GraphTable


def build_graph(n_per_side=12, seed=0):
    """Two communities; each node links to its 2 ring neighbors plus 2
    random same-community chords; one bridge edge."""
    rs = np.random.RandomState(seed)
    src, dst = [], []

    def ring(base):
        for i in range(n_per_side):
            a = base + i
            for d in (1, 2):
                b = base + (i + d) % n_per_side
                src.extend([a, b])
                dst.extend([b, a])
            c = base + rs.randint(n_per_side)
            if c != a:
                src.extend([a, c])
                dst.extend([c, a])

    ring(0)
    ring(n_per_side)
    src.extend([0, n_per_side])
    dst.extend([n_per_side, 0])
    g = GraphTable(seed=seed)
    g.add_edges(np.asarray(src, np.int64), np.asarray(dst, np.int64))
    return g, 2 * n_per_side


def skip_gram_pairs(walks, window=2):
    centers, contexts = [], []
    for walk in walks:
        walk = walk[walk >= 0]
        for i, c in enumerate(walk):
            lo, hi = max(0, i - window), min(len(walk), i + window + 1)
            for j in range(lo, hi):
                if j != i:
                    centers.append(c)
                    contexts.append(walk[j])
    return np.asarray(centers, np.int64), np.asarray(contexts, np.int64)


def train(g, n_nodes, dim=16, epochs=60, walks_per_node=6, walk_len=8,
          negatives=4, seed=0):
    paddle.seed(seed)
    emb_in = nn.Embedding(n_nodes, dim)
    emb_out = nn.Embedding(n_nodes, dim)
    optim = paddle.optimizer.Adam(
        learning_rate=0.05,
        parameters=list(emb_in.parameters()) + list(emb_out.parameters()))
    rs = np.random.RandomState(seed)

    losses = []
    for epoch in range(epochs):
        starts = np.tile(np.arange(n_nodes, dtype=np.int64), walks_per_node)
        walks = g.node2vec_walk(starts, walk_len, p=1.0, q=0.5)
        centers, contexts = skip_gram_pairs(walks)
        negs = rs.randint(0, n_nodes, (centers.size, negatives))

        c = emb_in(paddle.to_tensor(centers))           # [B, d]
        pos = emb_out(paddle.to_tensor(contexts))       # [B, d]
        neg = emb_out(paddle.to_tensor(negs))           # [B, k, d]
        pos_logit = (c * pos).sum(-1)
        neg_logit = (c.unsqueeze(1) * neg).sum(-1)      # [B, k]
        loss = (F.binary_cross_entropy_with_logits(
                    pos_logit, paddle.ones_like(pos_logit))
                + F.binary_cross_entropy_with_logits(
                    neg_logit, paddle.zeros_like(neg_logit)))
        loss.backward()
        optim.step()
        optim.clear_grad()
        losses.append(float(loss))
    return emb_in, losses


def community_margin(emb_in, n_nodes):
    """mean intra-community cosine sim minus mean inter-community sim."""
    vecs = emb_in.weight.numpy()
    vecs = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
    sims = vecs @ vecs.T
    half = n_nodes // 2
    # exclude the diagonal (self-similarity == 1.0) so intra measures
    # pairwise cohesion, not n self-matches inflating the mean
    intra = (sims[:half, :half][~np.eye(half, dtype=bool)].mean()
             + sims[half:, half:][~np.eye(n_nodes - half, dtype=bool)].mean()
             ) / 2
    inter = sims[:half, half:].mean()
    return float(intra - inter), float(intra), float(inter)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=60)
    ap.add_argument("--dim", type=int, default=16)
    args = ap.parse_args()

    g, n_nodes = build_graph()
    emb, losses = train(g, n_nodes, dim=args.dim, epochs=args.epochs)
    margin, intra, inter = community_margin(emb, n_nodes)
    print(f"loss {losses[0]:.4f} -> {losses[-1]:.4f}  "
          f"intra-sim {intra:.3f}  inter-sim {inter:.3f}  "
          f"margin {margin:.3f}")
    assert losses[-1] < losses[0]
    assert margin > 0.2, "communities failed to separate"


if __name__ == "__main__":
    main()
