"""step_temp_gib.train: the temporary memory of the compiled local step
the window runs (``step_sparse`` in a sparse cell, else
``step_plain``), in GiB: ``memory_analysis()`` of the program's own
jitted step.  After the run, the cell's trainer is built again from its
configuration and traffic (``Experiment``, as the driver builds it) and
its step is lowered for a client's first step and compiled: a read of
the persistent compile cache that the run filled.  It is what a
rematerialization setting bounds and what a change to the conv
lowering or to buffer donation moves.  Moves train_img_per_s."""


def read(ctx, red):
    import jax
    import jax.numpy as jnp
    from lib import harness
    from repro.experiment import Experiment
    from repro.optim import adam_init
    tr = Experiment(harness.load_driver(ctx.cell).Cell(ctx)._spec()).trainer
    step = tr.step_sparse if tr.step_sparse is not None \
        and ctx.cell.traffic.get("phase") == "sparse" else tr.step_plain
    batch = {k: jnp.asarray(v)
             for k, v in next(iter(tr.clients[0].data.epoch())).items()}
    ma = step.lower(tr.params, adam_init(tr.params), batch,
                    jax.random.PRNGKey(0), {}).compile().memory_analysis()
    return None if ma is None else ma.temp_size_in_bytes / 2**30
