"""repro.graph — the declarative BNN IR + compile pipeline.

Front door::

    from repro import graph
    cb = graph.compile(binarynet_cifar10())   # or a hand-built BNNSpec
    params = cb.init(jax.random.PRNGKey(0))
    logits = cb.apply(params, images)         # bit-identical to legacy
    print(cb.describe())                      # every lowering decision
    rows = cb.tulip_mapping()                 # the ASIC schedule model

See DESIGN.md §8 for the IR node set, pass order, and plan schema.
"""
from repro.graph.compile import (CompiledBNN, compile,
                                 compile_dense_stack,
                                 serve_folded_stack)
from repro.graph.ir import (Binarize, BinaryConv, BinaryDense, BNNSpec,
                            BNThreshold, IntegerEntry, Logits, MaxPool,
                            PoolFC, ResidualBlock, ResidualEntry,
                            from_dense_stack, from_workload,
                            reactnet_a_imagenet, residual_net,
                            spec_to_workload)
from repro.graph.passes import PlanStep, build_plan

__all__ = ["Binarize", "BinaryConv", "BinaryDense", "BNNSpec",
           "BNThreshold", "CompiledBNN", "IntegerEntry", "Logits",
           "MaxPool", "PlanStep", "PoolFC", "ResidualBlock",
           "ResidualEntry", "build_plan", "compile",
           "compile_dense_stack", "from_dense_stack", "from_workload",
           "reactnet_a_imagenet", "residual_net", "serve_folded_stack",
           "spec_to_workload"]
