"""Unified model-zoo runner — the `examples/run_<model>.py` scripts of the
reference (e.g. examples/gcn/run_gcn.py:46-84) folded into one CLI.

    python -m euler_tpu.examples.run_model --model gcn --dataset cora \
        --mode train --total-steps 200
    python -m euler_tpu.examples.run_model --model transe --dataset fb15k
    python -m euler_tpu.examples.run_model --model deepwalk --dataset cora

Model families (27-model zoo parity):
  conv supervised:   gcn sage gat agnn appnp arma sgcn tagcn dna gated
                     geniepath graph (examples/<name>)
  conv unsupervised: graphsage_unsup dgi gae vgae
  layerwise:         fastgcn adaptivegcn
  relation:          rgcn
  graph clf:         gin set2set gated_graph graphgcn
  embeddings:        deepwalk node2vec line
  knowledge graph:   transe transh transr transd distmult rotate
  scalable:          scalable_gcn scalable_sage

--synthetic uses each dataset's offline stand-in (this environment has no
network egress); with raw files in $EULER_TPU_DATA the real datasets load.
"""

from __future__ import annotations

import argparse

import numpy as np

CONV_MODELS = {
    "gcn": "gcn",
    "graphsage": "sage",
    "sage": "sage",
    "gat": "gat",
    "agnn": "agnn",
    "appnp": "appnp",
    "arma": "arma",
    "sgcn": "sgcn",
    "tagcn": "tagcn",
    "dna": "dna",
    "gated": "gated",
    "geniepath": "geniepath",
    "graph": "graph",
    "lgcn": "lgcn",
    "adaptivegcn": None,  # layerwise family
}
GRAPH_CLF = {"gin": ("gin", "mean"), "set2set": ("gin", "set2set"),
             "gated_graph": ("gated", "mean"), "graphgcn": ("gcn", "attention")}
KG_MODELS = {"transe", "transh", "transr", "transd", "distmult", "rotate"}


def build_parser():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", required=True)
    ap.add_argument("--dataset", default="cora")
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--synthetic", action="store_true")
    ap.add_argument("--mode", default="train",
                    choices=["train", "evaluate", "infer", "train_and_evaluate"])
    ap.add_argument("--model-dir", default="/tmp/euler_tpu_runs")
    ap.add_argument("--hidden-dim", type=int, default=32)
    ap.add_argument("--embedding-dim", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--fanouts", type=int, nargs="*", default=[10, 10])
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--total-steps", type=int, default=100)
    ap.add_argument("--learning-rate", type=float, default=0.01)
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--num-negs", type=int, default=5)
    ap.add_argument("--walk-len", type=int, default=5)
    ap.add_argument("--window", type=int, default=2)
    ap.add_argument("--p", type=float, default=1.0)
    ap.add_argument("--q", type=float, default=1.0)
    ap.add_argument("--log-steps", type=int, default=20)
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu) before device init")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-parallel", type=int, default=0,
                    help="devices for a data-parallel mesh (0 = single)")
    ap.add_argument("--device-flow", action="store_true",
                    help="sample batches ON the accelerator (HBM-resident "
                         "adjacency, zero per-step wire bytes) — conv "
                         "models, graphsage_unsup, rgcn, fastgcn/"
                         "adaptivegcn, gae/vgae/dgi, graph classification, "
                         "deepwalk/node2vec/line, and the TransX family; "
                         "local graphs only")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize conv layers on backward "
                         "(jax.checkpoint) — trades FLOPs for HBM on "
                         "deep stacks / wide fanouts")
    return ap


def _require_checkpoint(est):
    """evaluate/infer score TRAINED parameters; without this guard a
    missing checkpoint either crashes opaquely (params None on the
    embedding-family fast path) or silently scores random init."""
    if not est.restore():
        raise SystemExit(
            f"no checkpoint under {est.cfg.model_dir!r} — run --mode train "
            "with the same --model-dir first"
        )


def main(argv=None):
    from euler_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = build_parser().parse_args(argv)
    if args.platform:
        import jax  # must land before the first device query

        jax.config.update("jax_platforms", args.platform)
    from euler_tpu.datasets import get_dataset
    from euler_tpu.estimator import Estimator, EstimatorConfig, id_batches, node_batches
    from euler_tpu.graph import Graph

    rng = np.random.default_rng(args.seed)
    ds = get_dataset(args.dataset) if args.data_dir is None else None
    graph = (
        Graph.load(args.data_dir)
        if args.data_dir
        else ds.load_graph(synthetic=args.synthetic)
    )
    max_id = int(
        max(int(np.asarray(sh.node_ids).max(initial=0)) for sh in graph.shards)
    )

    cfg = EstimatorConfig(
        model_dir=f"{args.model_dir}/{args.model}_{args.dataset}",
        batch_size=args.batch_size,
        total_steps=args.total_steps,
        learning_rate=args.learning_rate,
        optimizer=args.optimizer,
        log_steps=args.log_steps,
        seed=args.seed,
    )
    mesh = None
    if args.data_parallel:
        from euler_tpu.parallel import make_mesh

        mesh = make_mesh(args.data_parallel)

    name = args.model
    feature = "feature"
    if args.remat and (
        name in KG_MODELS
        or name in ("deepwalk", "node2vec", "line", "fastgcn",
                    "adaptivegcn", "rgcn", "scalable_gcn", "scalable_sage")
    ):
        # embedding-table and dense-layerwise families have no conv stack
        # to rematerialize — say so instead of silently ignoring the flag
        print(f"# --remat has no effect for model {name!r} (no conv stack)")
    label_dim = getattr(ds, "num_classes", 2) if ds else 2
    dims = [args.hidden_dim] * args.layers
    flow = None  # set by families that evaluate/infer through a dataflow
    if args.device_flow and not (
        name in ("deepwalk", "node2vec", "line", "graphsage_unsup", "rgcn",
                 "fastgcn", "adaptivegcn", "gae", "vgae", "dgi")
        or name in KG_MODELS
        or name in GRAPH_CLF
        or (name in CONV_MODELS and CONV_MODELS[name])
    ):
        raise SystemExit(
            f"--device-flow is not implemented for model {name!r} — it "
            "covers conv models, graphsage_unsup, rgcn, fastgcn/"
            "adaptivegcn, gae/vgae/dgi, graph classification, deepwalk/"
            "node2vec/line, and the TransX family; rerun without the flag"
        )

    # ---- family dispatch -------------------------------------------------
    if name in KG_MODELS:
        from euler_tpu.models import TransX, kg_batches

        model = TransX(
            num_entities=max_id,
            num_relations=graph.meta.num_edge_types,
            dim=args.embedding_dim,
            variant=name,
        )
        if args.device_flow:
            from euler_tpu.dataflow import DeviceKGFlow

            bf = DeviceKGFlow(
                graph, args.batch_size, args.num_negs, mesh=mesh
            )
        else:
            bf = kg_batches(graph, args.batch_size, args.num_negs, rng=rng)
        est = Estimator(model, bf, cfg, mesh=mesh)
    elif name in ("deepwalk", "node2vec", "line"):
        from euler_tpu.models import SkipGramModel, deepwalk_batches, line_batches

        model = SkipGramModel(
            num_nodes=max_id, dim=args.embedding_dim,
            shared_context=(name == "line"),
        )
        if args.device_flow:
            from euler_tpu.dataflow import DeviceEdgeFlow, DeviceWalkFlow

            bf = (
                DeviceEdgeFlow(
                    graph, args.batch_size, args.num_negs, mesh=mesh
                )
                if name == "line"
                else DeviceWalkFlow(
                    graph, args.batch_size, args.walk_len, args.window,
                    args.num_negs, p=args.p if name == "node2vec" else 1.0,
                    q=args.q if name == "node2vec" else 1.0, mesh=mesh,
                )
            )
        else:
            bf = (
                line_batches(graph, args.batch_size, args.num_negs, rng=rng)
                if name == "line"
                else deepwalk_batches(
                    graph, args.batch_size, args.walk_len, args.window,
                    args.num_negs, p=args.p if name == "node2vec" else 1.0,
                    q=args.q if name == "node2vec" else 1.0, rng=rng,
                )
            )
        est = Estimator(model, bf, cfg, mesh=mesh)
    elif name in GRAPH_CLF:
        from euler_tpu.dataflow import WholeGraphDataFlow, graph_label_batches
        from euler_tpu.models import GraphClassifier

        conv, pool = GRAPH_CLF[name]
        flow = WholeGraphDataFlow(graph, [feature], max_nodes=16, max_degree=8, rng=rng)
        model = GraphClassifier(
            conv=conv, dims=tuple(dims),
            num_classes=max(flow.num_classes, 2), pool=pool,
            remat=args.remat,
        )
        if args.device_flow:
            from euler_tpu.dataflow import DeviceWholeGraphFlow

            bf = DeviceWholeGraphFlow(
                graph, [feature], batch_size=args.batch_size,
                mesh=mesh, host_flow=flow,
            )
        else:
            bf = graph_label_batches(graph, flow, args.batch_size, rng=rng)
        est = Estimator(model, bf, cfg, mesh=mesh)
    elif name in ("fastgcn", "adaptivegcn"):
        from euler_tpu.dataflow import LayerwiseDataFlow
        from euler_tpu.models import LayerwiseGCN

        flow = LayerwiseDataFlow(
            graph, [feature], layer_sizes=[64] * args.layers,
            label_feature="label", rng=rng,
        )
        model = LayerwiseGCN(dims=dims, label_dim=label_dim)
        if args.device_flow:
            from euler_tpu.dataflow import DeviceLayerwiseFlow

            bf = DeviceLayerwiseFlow(
                graph, [feature], batch_size=args.batch_size,
                layer_sizes=[64] * args.layers, label_feature="label",
                root_node_type=0, mesh=mesh,
            )
        else:
            bf = node_batches(graph, flow, args.batch_size, 0, rng=rng)
        est = Estimator(model, bf, cfg, mesh=mesh)
    elif name == "rgcn":
        from euler_tpu.dataflow import RelationDataFlow
        from euler_tpu.models import RGCNSupervised

        flow = RelationDataFlow(
            graph, [feature], num_relations=graph.meta.num_edge_types,
            fanout=args.fanouts[0], num_hops=args.layers,
            label_feature="label", rng=rng,
        )
        model = RGCNSupervised(
            dims=dims, num_relations=graph.meta.num_edge_types,
            label_dim=label_dim, num_bases=4,
        )
        if args.device_flow:
            from euler_tpu.dataflow import DeviceRelationFlow

            bf = DeviceRelationFlow(
                graph, [feature],
                num_relations=graph.meta.num_edge_types,
                batch_size=args.batch_size, fanout=args.fanouts[0],
                num_hops=args.layers, label_feature="label",
                root_node_type=0, mesh=mesh,
            )
        else:
            bf = node_batches(graph, flow, args.batch_size, 0, rng=rng)
        est = Estimator(model, bf, cfg, mesh=mesh)
    elif name in ("gae", "vgae"):
        from euler_tpu.dataflow import SageDataFlow
        from euler_tpu.models import GAE, gae_batches

        flow = SageDataFlow(graph, [feature], fanouts=args.fanouts[:1], rng=rng)
        model = GAE(
            dims=dims[:1], variational=(name == "vgae"), remat=args.remat
        )
        if args.device_flow:
            from euler_tpu.dataflow import DeviceGaeFlow
            from euler_tpu.estimator import DeviceFeatureCache

            est = Estimator(
                model,
                DeviceGaeFlow(graph, fanouts=args.fanouts[:1],
                              batch_size=args.batch_size, mesh=mesh),
                cfg, mesh=mesh,
                feature_cache=DeviceFeatureCache(graph, [feature]),
            )
        else:
            est = Estimator(
                model, gae_batches(graph, flow, args.batch_size, rng=rng),
                cfg, mesh=mesh,
            )
    elif name == "dgi":
        from euler_tpu.dataflow import SageDataFlow
        from euler_tpu.models import DGI, dgi_batches

        flow = SageDataFlow(graph, [feature], fanouts=args.fanouts[:1], rng=rng)
        model = DGI(dims=dims[:1], remat=args.remat)
        if args.device_flow:
            from euler_tpu.dataflow import DeviceDgiFlow
            from euler_tpu.estimator import DeviceFeatureCache

            est = Estimator(
                model,
                DeviceDgiFlow(graph, fanouts=args.fanouts[:1],
                              batch_size=args.batch_size, mesh=mesh),
                cfg, mesh=mesh,
                feature_cache=DeviceFeatureCache(graph, [feature]),
            )
        else:
            est = Estimator(
                model, dgi_batches(graph, flow, args.batch_size, rng=rng),
                cfg, mesh=mesh,
            )
    elif name in ("scalable_gcn", "scalable_sage"):
        from euler_tpu.models import ScalableGNN, ScalableTrainer

        model = ScalableGNN(dims=dims, label_dim=label_dim)
        trainer = ScalableTrainer(
            graph, model, [feature], max_id=max_id,
            batch_size=args.batch_size, fanout=args.fanouts[0],
            learning_rate=args.learning_rate, rng=rng,
        )
        hist = trainer.train(args.total_steps)
        print(f"final loss: {hist[-1]:.4f}")
        return 0
    elif name == "graphsage_unsup":
        from euler_tpu.dataflow import SageDataFlow
        from euler_tpu.estimator import unsupervised_batches
        from euler_tpu.models import GraphSAGEUnsupervised

        flow = SageDataFlow(graph, [feature], fanouts=args.fanouts[: args.layers], rng=rng)
        model = GraphSAGEUnsupervised(dims=dims, remat=args.remat)
        if args.device_flow:
            from euler_tpu.dataflow import DeviceUnsupSageFlow
            from euler_tpu.estimator import DeviceFeatureCache

            est = Estimator(
                model,
                DeviceUnsupSageFlow(
                    graph, fanouts=args.fanouts[: args.layers],
                    batch_size=args.batch_size, num_negs=args.num_negs,
                    mesh=mesh,
                ),
                cfg, mesh=mesh,
                feature_cache=DeviceFeatureCache(graph, [feature]),
            )
        else:
            est = Estimator(
                model,
                unsupervised_batches(
                    graph, flow, args.batch_size, num_negs=args.num_negs, rng=rng
                ),
                cfg, mesh=mesh,
            )
    elif name in CONV_MODELS and CONV_MODELS[name]:
        from euler_tpu.dataflow import SageDataFlow
        from euler_tpu.nn import SuperviseModel

        flow = SageDataFlow(
            graph, [feature], fanouts=args.fanouts[: args.layers],
            label_feature="label", rng=rng,
        )
        # the reference's GAT example defaults improved=True (run_gat.py
        # flags) — without it, zero-valid-neighbor roots in sampled flows
        # emit zero embeddings
        conv_kwargs = {"improved": True} if CONV_MODELS[name] == "gat" else None
        model = SuperviseModel(
            conv=CONV_MODELS[name], dims=dims, label_dim=label_dim,
            conv_kwargs=conv_kwargs, remat=args.remat,
        )
        if args.device_flow:
            from euler_tpu.dataflow import DeviceSageFlow
            from euler_tpu.estimator import DeviceFeatureCache

            est = Estimator(
                model,
                DeviceSageFlow(
                    graph, fanouts=args.fanouts[: args.layers],
                    batch_size=args.batch_size, label_feature="label",
                    root_node_type=0,  # node_batches(..., 0) parity
                    mesh=mesh,
                ),
                cfg, mesh=mesh,
                feature_cache=DeviceFeatureCache(graph, [feature]),
            )
        else:
            est = Estimator(
                model, node_batches(graph, flow, args.batch_size, 0, rng=rng),
                cfg, mesh=mesh,
            )
    else:
        raise SystemExit(f"unknown model {name!r}")

    # ---- drive ----------------------------------------------------------
    if args.mode != "train" and flow is None:
        import jax.numpy as jnp

        # reject an unsupported mode BEFORE demanding a checkpoint: the
        # "train first" advice would be a dead end for a mode this model
        # can never run
        kg_eval = name in KG_MODELS and args.mode == "evaluate"
        emb_infer = (
            name in ("deepwalk", "node2vec", "line") and args.mode == "infer"
        )
        if not (kg_eval or emb_infer):
            raise SystemExit(
                f"mode {args.mode!r} is not supported for model {name!r}"
            )
        _require_checkpoint(est)
        if kg_eval:
            from euler_tpu.models import kg_rank_eval

            if ds is not None and hasattr(ds, "eval_triples") and not args.synthetic:
                triples = ds.eval_triples("test")[:500]
            else:  # offline fallback: rank sampled training edges
                e = graph.sample_edge(200, rng=rng)
                triples = np.stack(
                    [e[:, 0], e[:, 2], e[:, 1]], axis=1
                ).astype(np.int32)
            print(kg_rank_eval(model, est.params, triples, num_entities=max_id))
            return 0
        if emb_infer:
            ids = np.concatenate(
                [np.asarray(sh.node_ids) for sh in graph.shards]
            )
            emb = np.asarray(
                model.apply(
                    est.params,
                    jnp.asarray(ids.astype(np.int64).astype(np.int32)),
                    method=model.embed,
                )
            )
            import os

            os.makedirs(cfg.model_dir, exist_ok=True)
            np.save(os.path.join(cfg.model_dir, "embedding_0.npy"), emb)
            np.save(os.path.join(cfg.model_dir, "ids_0.npy"), ids)
            print(f"wrote {emb.shape} embeddings to {cfg.model_dir}")
            return 0
    if args.mode == "train":
        hist = est.train()
        if len(hist):
            print(
                f"trained {len(hist)} steps; final loss {float(hist[-1]):.4f}"
            )
    elif args.mode == "train_and_evaluate":
        splits = ds.splits(graph) if ds else {"val": graph.sample_node(64)}
        batches_fn = lambda: id_batches(flow, splits["val"], args.batch_size)[0]  # noqa: E731
        print(est.train_and_evaluate(batches_fn, eval_every=max(args.total_steps // 2, 1)))
    elif args.mode == "evaluate":
        _require_checkpoint(est)
        splits = ds.splits(graph) if ds else {"test": graph.sample_node(64)}
        batches, _ = id_batches(flow, splits["test"], args.batch_size)
        print(est.evaluate(batches))
    elif args.mode == "infer":
        _require_checkpoint(est)
        splits = ds.splits(graph) if ds else {"test": graph.sample_node(64)}
        ids = np.concatenate(list(splits.values()))
        batches, chunks = id_batches(flow, ids, args.batch_size)
        idv, emb = est.infer(batches, chunks)
        print(f"wrote {emb.shape} embeddings to {cfg.model_dir}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
