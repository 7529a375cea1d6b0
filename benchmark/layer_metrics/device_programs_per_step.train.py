"""Device programs launched a training step: executions on the first chip's ``XLA Modules``
line over the traced steps, per execution of ``jit_train_step``. 1 is a loop that launches
only the step; every further program is an eager operation of the loop (an rng split, a
learning-rate schedule) with a dispatch and a transfer of its own on the host. Prints the
programs by name. Layer: train loop, host. Moves ``train_tokens_per_s_per_chip``.
"""


def read(result, ctx):
    if result.trace is None or "traced_steps" not in result.facts:
        return None
    names = result.trace.program_names()
    steps = names.get("jit_train_step", 0)
    if not steps:
        return None
    print(f"device_programs_per_step.train: {sum(names.values())} executions over {steps} steps: {names}", flush=True)
    return sum(names.values()) / steps
