"""A Program trained through the repo's normal path: Program ->
core/lowering.py -> Executor, or CompiledProgram.with_parallel on a mesh."""

import contextlib

import numpy as np

SEED_MODULUS = 2**31 - 1


class ProgramTrainer:
    """What the ``train_steps`` driver drives: ``step()`` dispatches one
    optimizer step on the repeated batch and returns the loss still on the
    device; nothing here waits for it."""

    def __init__(self, main, startup, loss, feed, seed, mesh_spec, samples):
        import paddle_tpu as fluid

        # the weights follow the seed; the step program does not: its
        # random_seed is part of its fingerprint, and a step that changed
        # with every seed would miss the compile cache's export tier and
        # retrace (10 s for BERT-base) in every run with a new seed
        startup.random_seed = seed % SEED_MODULUS + 1
        self.samples_per_step = samples
        self._feed = feed
        self._loss = loss
        self._scope = fluid.Scope()
        self._exe = fluid.Executor(fluid.TPUPlace(0))
        self._exe.run(startup, scope=self._scope)
        self._mesh = None
        self._program = main
        if mesh_spec:
            from paddle_tpu.parallel.env import make_mesh

            self._mesh = make_mesh(tuple(mesh_spec["shape"]),
                                   tuple(mesh_spec["axes"]))
            self._program = fluid.CompiledProgram(main).with_parallel(
                mesh=self._mesh, loss_name=loss.name)
        self.devices = (list(self._mesh.devices.flat) if self._mesh
                        else [fluid.TPUPlace(0).jax_device()])

    def step(self):
        return self._exe.run(self._program, feed=self._feed,
                             fetch_list=[self._loss], scope=self._scope,
                             return_numpy=False)[0]

    def compiled_bytes(self):
        """Bytes XLA's ``memory_analysis()`` gives the step that ran, per
        device: arguments + temporaries + outputs, less what is aliased.
        ``memory_stats()`` leaves a step's temporaries out on this runtime
        (PERF.md), so this is the cell's peak. Lowering the entry's own
        function again with the same abstract arguments resolves to the
        same executable; XLA's persistent cache serves the compile."""
        from paddle_tpu.core import lowering
        from paddle_tpu.parallel.env import mesh_context

        cache = (self._program._cache if self._mesh is not None
                 else self._exe._cache)
        (entry,) = (e for e in cache.values()
                    if self._loss.name in e.fetch_names)
        feed_sig = tuple((n, tuple(np.shape(self._feed[n])),
                          str(np.asarray(self._feed[n]).dtype))
                         for n in sorted(self._feed))
        ctx = (mesh_context(self._mesh) if self._mesh is not None
               else contextlib.nullcontext())
        with ctx:
            compiled = entry.lower(*lowering.abstract_signature(
                entry, feed_sig, self._scope)).compile()
        a = compiled.memory_analysis()
        return (a.argument_size_in_bytes + a.temp_size_in_bytes
                + a.output_size_in_bytes - a.alias_size_in_bytes)

    def close(self):
        self._exe.close()
