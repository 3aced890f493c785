"""Server's programs carry their names into the device trace: every jitted
program of a paged server with speculation on lowers to the module
`jit_serve_<key>`, the name its runs have on the device's "XLA Modules" line
(bench/programs.py reads them there)."""
import dataclasses

import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.serve import Server, serve_params


def test_every_program_lowers_to_its_serve_name():
    cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(),
                              policy="int8")
    params, _ = serve_params(cfg, seed=0, plane_twins=True)
    srv = Server(cfg, params, slots=2, cache_len=32, page_size=4,
                 spec_draft="planes:1", spec_k=3)
    assert srv.spec
    P, M = srv.phys_slots, srv.max_pages

    def z(*shape):
        return jnp.zeros(shape, jnp.int32)

    decode = (srv.params, srv.cache, z(P, 1), z(P), z(P, M))
    args = {
        "prefill": (srv.params, z(1, srv.buckets[0]), z(1)),
        "decode": decode,
        "draft": decode,
        "cow": (srv.cache, jnp.int32(0), jnp.int32(1)),
        "chunk": (srv.params, srv.cache, z(1, 4), z(1), z(1, M), z(1, M),
                  z(1), z(1)),
        "verify": (srv.params, srv.cache, z(P, srv.spec_k), z(P), z(P, M),
                   z(P, M), z(P)),
    }
    assert set(args) == set(srv.compile_counts)
    for key, a in args.items():
        head = getattr(srv, "_" + key).lower(*a).as_text().split("\n", 1)[0]
        assert f"module @jit_serve_{key} " in head, (key, head)
