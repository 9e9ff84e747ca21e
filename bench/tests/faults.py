"""Faults planted in the program under test, for the tests that see
``correct`` come out false.  Each breaks the timed path underneath the
harness, as a faulty program change would."""
from __future__ import annotations


def _frozen():
    """A step that returns its state unchanged."""
    from repro.launch import steps
    make = steps.make_train_step

    def make_frozen(cfg, opt, **kw):
        real = make(cfg, opt, **kw)

        def step(params, opt_state, batch):
            _, _, metrics = real(params, opt_state, batch)
            return params, opt_state, metrics
        return step
    steps.make_train_step = make_frozen


def _half_batch():
    """Half of the batch left out, the mean taken over the rest."""
    from repro.launch import steps
    loss = steps.lm_loss

    def half(cfg, params, batch, **kw):
        return loss(cfg, params, {k: v[:v.shape[0] // 2]
                                  for k, v in batch.items()}, **kw)
    steps.lm_loss = half


def _no_exchange():
    """Muon's NS chain on each chip's column shard with no exchange
    between chips."""
    import jax
    import jax.numpy as jnp
    from repro.optim import muon

    def local(self, m2):
        n = self.mesh.shape[self.axis]
        transpose = m2.shape[-2] > m2.shape[-1]
        x = m2.swapaxes(-1, -2) if transpose else m2
        if x.shape[-1] % n:
            return muon.orthogonalize_reference(m2, self.ns_steps)
        parts = jnp.split(x, n, axis=-1)
        out = jnp.concatenate(
            [muon.orthogonalize_reference(p, self.ns_steps) for p in parts],
            axis=-1)
        return out.swapaxes(-1, -2) if transpose else out
    muon.Muon._orthogonalize = local
    del jax


PLANTS = {"frozen": _frozen, "half_batch": _half_batch,
          "no_exchange": _no_exchange}


def plant(name: str) -> None:
    PLANTS[name]()
