"""Production-mapping demo: the ProFe gossip round as TPU collectives.

Runs the actual multi-pod federation program (quantize -> int16 exchange
over the ``pod`` axis -> Eq. 4 aggregation) on a host mesh with 8
simulated devices, and prints the collective schedule the 512-chip
dry-run sees.

    PYTHONPATH=src python examples/mesh_federation_demo.py

The demo runs on the CPU whatever the host holds: ``main`` pins the
CPU platform and 8 host devices before the first jax call.
"""
import jax
import jax.numpy as jnp

from repro.config import get_config
from repro.core.mesh_federation import make_fedavg_round, make_profe_round
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_mesh
from repro.launch.wire import ensure_host_device_flag
from repro.models import derive_student, init_params
from repro.sharding import param_specs


def main():
    jax.config.update("jax_platforms", "cpu")
    ensure_host_device_flag(8)
    enable_compile_cache()
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    print(f"mesh: {dict(mesh.shape)} = {mesh.devices.size} devices")

    cfg = get_config("yi-6b").smoke()
    student_cfg = derive_student(cfg)
    s0 = init_params(student_cfg, jax.random.PRNGKey(0))
    s1 = init_params(student_cfg, jax.random.PRNGKey(1))
    students = jax.tree_util.tree_map(lambda a, b: jnp.stack([a, b]), s0, s1)
    shapes = jax.eval_shape(lambda: init_params(student_cfg,
                                                jax.random.PRNGKey(0)))
    specs = param_specs(student_cfg, shapes, mesh)

    C, Pdim = cfg.n_proto_classes, student_cfg.proto_dim
    protos = jnp.stack([jnp.ones((C, Pdim)), 2 * jnp.ones((C, Pdim))])
    counts = jnp.ones((2, C))
    sizes = jnp.asarray([1.0, 3.0])  # node 1 has 3x the data

    round_fn = make_profe_round(mesh, specs, bits=16)
    with mesh:
        jitted = jax.jit(round_fn)
        lowered = jitted.lower(students, protos, counts, sizes)
        compiled = lowered.compile()
        an = analyze_hlo(compiled.as_text())
        print("\nProFe gossip collective schedule (per device):")
        for k, v in sorted(an.coll.items()):
            if v:
                print(f"  {k:20s} {v/1e6:8.2f} MB")
        new_students, glob, mask = jitted(students, protos, counts, sizes)

    # aggregation check: size-weighted mean 0.25*s0 + 0.75*s1
    leaf = jax.tree_util.tree_leaves(new_students)[0]
    want = 0.25 * jax.tree_util.tree_leaves(s0)[0] + \
        0.75 * jax.tree_util.tree_leaves(s1)[0]
    err = float(jnp.max(jnp.abs(leaf[0] - want)))
    print(f"\naggregated student max err vs exact weighted mean: {err:.2e} "
          f"(16-bit wire quantization)")
    print(f"global prototypes: C̄[0,0] = {float(glob[0, 0]):.3f} "
          f"(equal counts -> 1.5)")

    fed_fn = make_fedavg_round(mesh, param_specs(
        cfg, jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))),
        mesh))
    teachers = jax.tree_util.tree_map(
        lambda a: jnp.stack([a, a]), init_params(cfg, jax.random.PRNGKey(2)))
    with mesh:
        cf = jax.jit(fed_fn).lower(teachers, sizes).compile()
        an_f = analyze_hlo(cf.as_text())
    profe_b = an.coll_total
    fedavg_b = an_f.coll_total
    if fedavg_b > 0:
        print(f"\nwire bytes/device: ProFe {profe_b/1e6:.2f} MB vs "
              f"FedAvg {fedavg_b/1e6:.2f} MB  "
              f"(-{1 - profe_b / fedavg_b:.0%})")
    else:
        print("\n(XLA elided the tiny-model collectives on this host "
              "mesh; run the 512-device dry-run for the real schedule)")

    # --- neighborhood-masked gossip: a sparse graph on the pod axis ----
    from repro.core import topology as T
    adj = T.adjacency(2, "star")       # node 1 only hears the hub
    ring_fn = make_profe_round(mesh, specs, bits=16, adjacency=adj)
    with mesh:
        s_masked, glob_n, _ = jax.jit(ring_fn)(students, protos, counts,
                                               sizes)
    leaf_m = jax.tree_util.tree_leaves(s_masked)[0]
    div = float(jnp.max(jnp.abs(leaf_m[0] - leaf_m[1])))
    print(f"\nmasked 'star' gossip: per-node prototypes {glob_n.shape}, "
          f"node divergence {div:.2e} (sparse graphs keep nodes distinct)")

    # --- physical sparse gossip: ppermute ring on a federation mesh ----
    # one device per node: the packed int16 buffer rides degree-many
    # collective-permutes, so a ring moves O(degree), not O(N), bytes
    from repro.launch.wire import fed_mesh as make_fed_mesh
    n = 8
    fed_mesh = make_fed_mesh(n)
    ring = T.adjacency(n, "ring")
    stud8 = jax.tree_util.tree_map(
        lambda a: jnp.concatenate([a] * (n // a.shape[0]))[:n], students)
    protos8 = jnp.concatenate([protos] * (n // 2))[:n]
    counts8 = jnp.concatenate([counts] * (n // 2))[:n]
    sizes8 = jnp.ones((n,))
    wire_bytes = {}
    for ex in ("packed", "ppermute"):
        fn = make_profe_round(fed_mesh, specs, bits=16,
                              adjacency=None if ex == "packed" else ring,
                              exchange=ex)
        with fed_mesh:
            args = (stud8, protos8, counts8, sizes8)
            an_x = analyze_hlo(
                jax.jit(fn).lower(*args).compile().as_text())
        wire_bytes[ex] = an_x.coll_total
    print(f"\nphysical wire, N=8 federation mesh: full all-gather "
          f"{wire_bytes['packed']/1e6:.2f} MB/node vs ppermute ring "
          f"{wire_bytes['ppermute']/1e6:.2f} MB/node "
          f"({wire_bytes['ppermute']/wire_bytes['packed']:.1%} — physical "
          f"bytes match the logical ring)")


if __name__ == "__main__":
    main()
