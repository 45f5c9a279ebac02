"""Model operations per example times the examples per second of this run's
own untraced window, over chips times the bf16 peak."""


def read(ctx):
    cell = ctx["cell"]
    per_example = ctx["flops"].train_flops_per_example(cell["config"],
                                                       cell["traffic"])
    return (100.0 * per_example * ctx["measured"]["train_examples_per_s"]
            / (cell["workload"]["chips"] * ctx["peaks"]["bf16_flops_per_s"]))
