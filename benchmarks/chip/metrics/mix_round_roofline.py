"""Roofline share of the Pallas mixing round: the least time one round
can take (``flops.round_bytes``: read and write the node-stacked (n, D)
state once in the wire dtype, over the HBM peak) over the summed device
time of the round's kernels per round.  The round's kernels are the
Pallas custom calls of a train-step program whose first result is an
(n, D) float32 matrix; the staging copies around them are not counted.
None where no such kernel ran."""


def read(ctx):
    from chip import flops, tracered
    n = ctx["n_nodes"]
    prefix = f"f32[{n},"
    t_ns, rounds = tracered.step_kernel_time(
        ctx["trace"], ctx["red"], lambda o: o["kernel"].startswith(prefix))
    if not rounds:
        return None
    least_s = flops.round_bytes(n, ctx["params_per_node"],
                                ctx["comm_itemsize"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (t_ns * 1e-9 / rounds)
