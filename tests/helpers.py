"""Shared test helpers (imported as a plain module, not via conftest)."""

import contextlib
import signal
import socket


class PortReservation:
    """Race-free test-port reservation.

    The old idiom — bind a probe to port 0, read the port, close the
    probe, hand the number to a subprocess that rebinds it — has a
    window in which any other process can grab the port (the bind-race
    flake class). This helper keeps the reservation socket BOUND (and
    never listening) for its whole lifetime:

      - While held, no other bind can take the port, and connects to it
        are refused — ideal for "nothing listens here" tests.
      - A server that binds with ``reuse_port=True`` (SO_REUSEPORT,
        e.g. ``PreemptionLeader(reuse_port=True)``) can bind WHILE the
        reservation is held: a bound-but-not-listening socket is not in
        the kernel's listen group, so every connection goes to the real
        listener — the race is eliminated, not narrowed.
      - Servers that cannot set SO_REUSEPORT (jax.distributed's
        coordinator, a LearnerServer inside a spawned run) call
        ``release()`` at the last moment before the bind — the window
        shrinks to the handoff instant and lives in ONE audited place
        instead of being re-derived per test.
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        if hasattr(socket, "SO_REUSEPORT"):
            self._sock.setsockopt(
                socket.SOL_SOCKET, socket.SO_REUSEPORT, 1
            )
        self._sock.bind((host, port))
        self.host = host
        self.port = self._sock.getsockname()[1]

    @classmethod
    def hold(cls, host: str, port: int) -> "PortReservation":
        """Re-reserve a SPECIFIC just-freed port — the dead-peer
        guarantee. A test that closes a server and keeps using its
        port as a "nothing listens here" address (the probe-close
        residue of the old idiom) races every other process on the
        box: anyone can rebind the freed port and turn "connection
        refused" into "connected to a stranger". Holding the port
        bound-but-never-listening the moment the server dies keeps it
        refusing for the rest of the test. (SO_REUSEADDR clears the
        listener's TIME_WAIT residue.)"""
        return cls(host, port)

    def release(self) -> int:
        """Close the reservation (just-in-time handoff for servers
        that cannot share the port via SO_REUSEPORT); returns the
        port. Idempotent."""
        if self._sock is not None:
            try:
                self._sock.close()
            finally:
                self._sock = None
        return self.port

    def __enter__(self) -> "PortReservation":
        return self

    def __exit__(self, *exc) -> None:
        self.release()


def reserve_port(host: str = "127.0.0.1") -> PortReservation:
    """Reserve an ephemeral test port; see ``PortReservation``."""
    return PortReservation(host)

import jax
import jax.numpy as jnp


@contextlib.contextmanager
def time_limit(seconds: float, what: str = "test"):
    """Hard wall-clock guard for socket/thread tests: a hang raises
    ``TimeoutError`` in the main thread (SIGALRM) instead of wedging
    the whole suite. No-op off the main thread or without SIGALRM."""
    if not hasattr(signal, "SIGALRM"):
        yield
        return
    def _raise(signum, frame):
        raise TimeoutError(f"{what} exceeded {seconds}s")

    try:
        prev = signal.signal(signal.SIGALRM, _raise)
    except ValueError:  # not on the main thread
        yield
        return
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, prev)

from actor_critic_algs_on_tensorflow_tpu import envs as envs_lib
from actor_critic_algs_on_tensorflow_tpu.algos import common
from actor_critic_algs_on_tensorflow_tpu.models import DiscreteActorCritic


def greedy_cartpole_return(params, model=None):
    """Shared greedy-eval harness for the CartPole learning tests:
    argmax policy over 32 envs, full 500-step horizon. ``model`` must
    match the architecture ``params`` was trained with (defaults to the
    stock ``DiscreteActorCritic`` the learning tests all use). Returns
    (mean_return, fraction_of_envs_finished) as floats."""
    env, env_params = envs_lib.make("CartPole-v1", num_envs=32)
    if model is None:
        model = DiscreteActorCritic(num_actions=2)

    def act(obs, key):
        logits, _ = model.apply(params, obs)
        return jnp.argmax(logits, axis=-1)

    mean_ret, _, frac_done = jax.jit(
        lambda key: common.evaluate(
            env, env_params, act, key, num_envs=32, max_steps=501
        )
    )(jax.random.PRNGKey(123))
    return float(mean_ret), float(frac_done)

import time as _time


def wait_registered(server, *expect, hellos=None, timeout=5.0):
    """Poll a ``LearnerServer``'s hello registry until it settles.

    Hellos register asynchronously on each connection's serve thread,
    so a registry/membership assertion issued right after connect races
    them (the async-hello flake class first hardened ad hoc inside
    ``test_membership_and_reshard_wire_kinds``). ``expect`` is any
    number of ``(actor_id, generation)`` pairs that must ALL appear in
    ``server.connections()``; ``hellos`` additionally waits for
    ``transport_hellos >= hellos``. Returns the settled connection
    rows; raises ``AssertionError`` on timeout so the failure names
    what never registered instead of surfacing as a downstream
    ``KeyError``."""
    want = {(int(a), int(g)) for a, g in expect}
    deadline = _time.monotonic() + timeout
    while True:
        rows = server.connections()
        seen = {(r["actor_id"], r["generation"]) for r in rows}
        if want <= seen and (
            hellos is None
            or server.metrics()["transport_hellos"] >= hellos
        ):
            return rows
        if _time.monotonic() >= deadline:
            raise AssertionError(
                f"hellos never registered: want {sorted(want)} "
                f"(hellos>={hellos}), have {sorted(seen)}"
            )
        _time.sleep(0.01)


def wait_member_rows(client, expect, *, seq=0, timeout=5.0):
    """Wire-side twin of ``wait_registered``: poll
    ``client.membership_request`` until every ``(actor_id,
    generation)`` pair in ``expect`` appears in the reply rows.
    Returns the final ``(rows, hellos, epoch)`` reply."""
    want = {(int(a), int(g)) for a, g in expect}
    deadline = _time.monotonic() + timeout
    while True:
        rows, hellos, epoch = client.membership_request(seq=seq)
        seen = {(r[0], r[1]) for r in rows if r[0] >= 0}
        if want <= seen:
            return rows, hellos, epoch
        if _time.monotonic() >= deadline:
            raise AssertionError(
                f"hellos never registered: want {sorted(want)}, "
                f"have {sorted(seen)}"
            )
        _time.sleep(0.01)


def chunk_matrices(lead, c, repeated, seed=0):
    """``a [*lead, c, c]`` as the chunked delta rule builds it: ``(k beta)
    k^T`` of l2-normed keys under the decay, strictly lower. With
    ``repeated`` keys a chunk draws its keys from four, as the recall
    env's traffic does, and ``beta`` is near 1: entries near 1."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape, d = lead + (c,), 16
    if repeated:
        vocab = jax.random.normal(ks[0], lead + (4, d))
        pick = jax.random.randint(ks[1], shape, 0, 4)
        k = jnp.take_along_axis(vocab, pick[..., None], axis=-2)
        beta = jax.nn.sigmoid(3.0 + jax.random.normal(ks[2], shape))
        g = -0.01 * jax.random.uniform(ks[3], shape)
    else:
        k = jax.random.normal(ks[0], shape + (d,))
        beta = jax.nn.sigmoid(jax.random.normal(ks[2], shape))
        g = -jax.random.uniform(ks[3], shape, maxval=2.0)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    gc = jnp.cumsum(g, -1)
    lower = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(
        jnp.where(lower, gc[..., :, None] - gc[..., None, :], 0.0)
    ) * lower
    a = jnp.einsum("...id,...jd->...ij", k * beta[..., None], k,
                   precision=jax.lax.Precision.HIGHEST)
    return a * decay * jnp.tril(lower, -1)


def kernels_interpreted(monkeypatch, **sizes):
    """Off the chip, every Pallas kernel a model chooses where its
    operands fit, as a program lowered for a TPU takes it, the body in
    the Pallas interpreter: ``ops/kernel_choice.py``'s one seam replaced.
    ``sizes`` (``chunk=8``, ``tile_q=32``, ...) go to each function of
    the Pallas module that takes them, ``fits`` among them."""
    import functools
    import importlib
    import inspect

    from actor_critic_algs_on_tensorflow_tpu.ops import kernel_choice

    given = {"interpret": True, **sizes}

    class Interpreted:
        def __init__(self, module):
            self._module = module

        def __getattr__(self, name):
            f = getattr(self._module, name)
            if not inspect.isfunction(f):
                return f
            params = inspect.signature(f).parameters
            anything = any(p.kind is p.VAR_KEYWORD for p in params.values())
            return functools.partial(f, **{
                k: v for k, v in given.items() if anything or k in params
            })

    def lowered_for(name):
        module = importlib.import_module(f"{kernel_choice.__package__}.{name}")
        return Interpreted(module), (
            lambda kernel, plain, *operands: kernel(*operands)
        )

    monkeypatch.setattr(kernel_choice, "_lowered_for", lowered_for)
