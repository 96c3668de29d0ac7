"""skylark-graph-se: approximate adjacency spectral embedding driver.

≙ ``ml/skylark_graph_se.cpp`` (arc-list → ASE → embeddings file).
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def _integer_arcs(path):
    """``(vertices, u, v)`` of an arc list whose vertex names are all
    integers: the distinct ids ascending and every arc's ends as positions
    among them; None where a name is not an integer.  Self-loops are
    dropped as the parser drops them for ``SimpleGraph``."""
    from ..io.arclist import _chunk_lines, _parse_edge_block
    from ..io.source import open_source

    us: list[str] = []
    vs: list[str] = []
    for block in _chunk_lines(open_source(path), 8 << 20):
        bu, bv = _parse_edge_block(block)
        us.extend(bu)
        vs.extend(bv)
    try:
        ids = np.array(us + vs, dtype=np.int64)
    except (ValueError, OverflowError):
        return None
    vertices, at = np.unique(ids, return_inverse=True)
    return vertices, at[: len(us)].astype(np.int32), at[len(us):].astype(np.int32)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="skylark-graph-se")
    p.add_argument("graphfile", help="arc-list file")
    p.add_argument("--rank", "-k", type=int, default=8)
    p.add_argument("--seed", type=int, default=38734)
    p.add_argument("--num-iterations", "-i", type=int, default=2)
    p.add_argument("--sparse", action="store_true")
    p.add_argument(
        "--streamed", action="store_true",
        help="one-pass streamed Nystrom ASE: folds edge blocks, never "
        "materializes the adjacency (forces --num-iterations 0)",
    )
    p.add_argument("--batch-edges", type=int, default=65536)
    p.add_argument("--prefix", default="embedding")
    args = p.parse_args(argv)

    from ..core.context import SketchContext
    from ..core.sparse import prepare
    from ..graph import (
        ASEParams, adjacency_from_edges, approximate_ase, read_arc_list)

    # --sparse on an arc list of integers: the adjacency and the product's
    # layout of it are built on the device, here, where the graph is made
    arcs = _integer_arcs(args.graphfile) if args.sparse and not args.streamed else None
    if arcs is not None:
        vertices, u, v = arcs
        A = adjacency_from_edges(u, v, len(vertices))
        G = prepare(A, symmetric=True)
        print(f"Read graph: {len(vertices)} vertices, {A.nse // 2} edges "
              "(adjacency built on the device)")
    else:
        G = read_arc_list(args.graphfile)
        vertices = G.vertices
        print(f"Read graph: {G.n} vertices, {G.volume // 2} edges")
    if args.streamed:
        params = ASEParams(
            num_iterations=0, streamed=True, batch_edges=args.batch_edges
        )
    else:
        params = ASEParams(
            num_iterations=args.num_iterations, sparse=args.sparse
        )
    t0 = time.perf_counter()
    (X, lam), info = approximate_ase(
        G,
        args.rank,
        SketchContext(seed=args.seed),
        params,
        return_info=True,
    )
    X = np.asarray(X)
    seconds = time.perf_counter() - t0
    np.save(f"{args.prefix}.X.npy", X)
    with open(f"{args.prefix}.index.txt", "w") as f:
        for v in vertices:
            f.write(f"{v}\n")
    if "products" in info:
        print(f"{info['products']} products with the adjacency, "
              f"{seconds:.3f} s the call")
    print(f"Embeddings ({X.shape[0]}x{args.rank}) -> {args.prefix}.X.npy; "
          f"eigenvalues: {np.asarray(lam)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
