"""Driver of the ``write_read`` traffic kind.

A closed loop: each iteration is one collective write of every rank's
requests through the user entry point the traffic names
(``make_tam_write`` or ``make_twophase_write``), then one planned
collective read (``make_twophase_read``) of the file that write
produced, as a restart reads it back. Both programs are planned and
compiled once in set-up, at the sizes the window runs.

The request structure is the configuration's pattern and does not
depend on the seed; the seed draws the payload bytes, on the device,
in one jitted call. Every payload byte is nonzero, so a request that is
dropped shows in the file as zeros.
"""
from __future__ import annotations

import time
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import roofline
import yardstick as ys
from repro.core.domains import contiguous_layout
from repro.core.plan import IOConfig
from repro.core.tam import make_tam_write
from repro.core.twophase import make_twophase_read, make_twophase_write
from repro.launch.mesh import make_io_mesh

WRITERS = {"tam": make_tam_write, "twophase": make_twophase_write}
PATTERNS = {"e3sm_g": ys.e3sm_g_requests}
RANK_AXES = ("node", "lagg", "lmem")


def seed_key(seed: int) -> np.ndarray:
    """A raw threefry key from any whole number (JAX's own ``PRNGKey``
    keeps only the low 32 bits of a Python int)."""
    return np.random.SeedSequence(seed % 2**128).generate_state(2, np.uint32)


def _payload(key, shape):
    bits = jax.random.bits(key, shape, jnp.uint32) | jnp.uint32(0x01010101)
    return jax.lax.bitcast_convert_type(bits, jnp.int32)


class Cell:
    """Set-up, window iterations and the correctness check of one
    ``write_read`` cell. ``wrap``, if given, takes this object after
    compilation and may replace ``self.write``/``self.read`` (the
    control and the fault tests put broken programs there)."""

    def __init__(self, config: dict, traffic: dict, devices, seed: int,
                 wrap=None):
        t_in = time.perf_counter()
        mesh = make_io_mesh(*config["mesh"], devices=devices)
        n_ranks, n_nodes = mesh.size, mesh.shape["node"]
        reqs = config["rank_requests"]
        req_elems = config["request_bytes"] // ys.ELEM_BYTES
        cb = config["cb_buffer_bytes"] // ys.ELEM_BYTES
        offsets, lengths = PATTERNS[config["pattern"]](
            n_ranks, reqs, config["request_bytes"], config["merged_ranks"],
            config["interleave_ranks"])
        self.offsets = (offsets // ys.ELEM_BYTES).astype(np.int32)
        self.lengths = (lengths // ys.ELEM_BYTES).astype(np.int32)
        self.counts = np.full((n_ranks,), reqs, np.int32)
        extent = int((self.offsets + self.lengths).max())
        self.layout = contiguous_layout(
            ys.padded_file_elems(extent, n_nodes, cb), n_nodes)
        self.work = roofline.io_bytes(self.offsets, self.lengths,
                                      self.counts, self.layout.file_len,
                                      n_nodes)
        self.requested_bytes = self.work["payload"]

        ranks = NamedSharding(mesh, P(RANK_AXES))
        data = jax.jit(_payload, static_argnums=1, out_shardings=ranks)(
            seed_key(seed), (n_ranks, reqs * req_elems))
        self.args = (jax.device_put(self.offsets, ranks),
                     jax.device_put(self.lengths, ranks),
                     jax.device_put(self.counts, ranks), data)

        jax.block_until_ready(self.args)
        self.inputs_s = time.perf_counter() - t_in
        cfg = IOConfig(req_cap=reqs, data_cap=reqs * req_elems,
                       cb_buffer_size=cb,
                       pipeline=config["pipeline_depth"] > 1,
                       pipeline_depth=config["pipeline_depth"])
        t0 = time.perf_counter()
        write_fn = WRITERS[traffic["write"]](mesh, self.layout, cfg)
        read_fn = make_twophase_read(mesh, self.layout, cfg)
        t1 = time.perf_counter()
        file_spec = jax.ShapeDtypeStruct(
            (n_nodes, self.layout.file_len // n_nodes), jnp.int32,
            sharding=NamedSharding(mesh, P("node")))
        self.write = jax.jit(write_fn).lower(*self.args).compile()
        self.read = jax.jit(read_fn).lower(*self.args[:3],
                                           file_spec).compile()
        t2 = time.perf_counter()
        self.plan_s, self.compile_s = t1 - t0, t2 - t1
        self.kept: list = []
        if wrap is not None:
            wrap(self)

    def iterate(self, span) -> list[dict]:
        """One write and one read of its file; returns the two calls."""
        calls = []
        with span("write"):
            t0 = time.perf_counter()
            with span("dispatch"):
                out = self.write(*self.args)
            with span("wait"):
                file, _ = jax.block_until_ready(out)
            calls.append({"kind": "write", "bytes": self.requested_bytes,
                          "wall_s": time.perf_counter() - t0})
        with span("read"):
            t0 = time.perf_counter()
            with span("dispatch"):
                got = self.read(*self.args[:3], file)
            with span("wait"):
                got = jax.block_until_ready(got)
            calls.append({"kind": "read", "bytes": self.requested_bytes,
                          "wall_s": time.perf_counter() - t0})
        with span("keep"):
            self.kept.append((np.asarray(file), np.asarray(got)))
        return calls

    def check(self) -> tuple[dict, int]:
        """Free the device state, then compare every kept file with the
        plain reference and every read with the payload it must return.
        Returns ``({number: (value, limit)}, calls that failed)``."""
        payload = np.asarray(self.args[3])
        self.args = self.write = self.read = None
        ref = ys.write_reference(self.layout.file_len, self.offsets,
                                 self.lengths, self.counts, payload)
        file_wrong = [ys.bytes_differing(f, ref) for f, _ in self.kept]
        read_wrong = [ys.bytes_differing(g, payload) for _, g in self.kept]
        failed = sum(x > 0 for x in file_wrong + read_wrong)
        return ({"file_bytes_wrong": (sum(file_wrong), 0),
                 "read_bytes_wrong": (sum(read_wrong), 0)}, failed)
