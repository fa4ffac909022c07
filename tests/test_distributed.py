"""Two-host distributed convergence: OS processes, each with its OWN
virtual device mesh and sharded device weave, exchanging nodes over a
real socket via the anti-entropy sync protocol.

This is the framework's full distributed stack in one test — the
DCN-analogue (host-level version-vector sync over a byte stream,
sync.py) composed with the ICI-analogue (sharded merge+weave with
psum collectives over a jax Mesh, parallel/mesh.py) — run as actual
separate processes, not simulated sites in one interpreter. Each host
edits its replicas, syncs with the peer, then computes convergence
digests ON ITS OWN MESH; the digests must agree across hosts.

Reference analogue: none (the reference's distribution is node
exchange only, README.md:5; shared.cljc:300-314 merges locally). The
multi-host composition is this framework's §5.8 obligation.
"""

import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HOST_PROG = r"""
import os, sys, socket
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, {root!r})
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

import cause_tpu as c
from cause_tpu import sync
from cause_tpu.collections import clist as c_list
from cause_tpu.collections.clist import CausalList
from cause_tpu.benchgen import LANE_KEYS5
from cause_tpu.ids import new_site_id
from cause_tpu.parallel.mesh import make_mesh, sharded_merge_weave_v5
from cause_tpu.weaver import lanecache
from cause_tpu.weaver.segments import concat_seg_tables
from cause_tpu.weaver.arrays import next_pow2
from cause_tpu import benchgen

host_id = int(sys.argv[1])
port = int(sys.argv[2])

# shared document every host starts from: identity AND content must be
# deterministic across processes (fixed uuid + fixed authoring site),
# exactly like two real hosts loading the same document snapshot
base = CausalList(c_list.new_causal_tree("jax").evolve(
    uuid="shareddoc0000000000xx", site_id="seedsite00000"))
base = base.extend([f"doc{{i}}" for i in range(40)])
base = CausalList(c_list.weave(base.ct))

# each host edits its own fleet of replicas under distinct sites
replicas = []
for r in range(4):
    rep = CausalList(base.ct.evolve(site_id=f"h{{host_id}}r{{r}}{{'_' * 9}}"))
    rep = rep.extend([f"h{{host_id}}.{{r}}.{{i}}" for i in range(3)])
    rep = rep.append(rep.tail_id(), c.hide)
    replicas.append(rep)

# merge the local fleet, then sync the result with the peer over TCP
local = replicas[0].merge_many(replicas[1:])
if host_id == 0:
    srv = socket.create_server(("127.0.0.1", port))
    print("LISTENING", flush=True)
    conn, _ = srv.accept()
else:
    import time as _time
    for attempt in range(60):
        try:
            conn = socket.create_connection(("127.0.0.1", port),
                                            timeout=30)
            break
        except OSError:
            _time.sleep(0.5)
    else:
        raise SystemExit("peer never came up")
stream = conn.makefile("rwb")
merged = sync.sync_stream(local, stream)

# device check on THIS host's own 4-device mesh: weave the converged
# tree (against the shared base) with the sharded v5 kernel + psum
# digest, replicated across mesh rows
mesh = make_mesh(4)
va = lanecache.view_for(merged.ct)
vb = lanecache.view_for(base.ct)
cap = next_pow2(max(va.n, vb.n))
from cause_tpu.parallel.wave import _assemble_rows
lanes = _assemble_rows([(va, vb)] * 4, cap)
u = benchgen.v5_token_budget(lanes)
rank, visible, overflow, digest, total_vis, n_conf, n_ovf = (
    sharded_merge_weave_v5(
        mesh, {{k: lanes[k] for k in LANE_KEYS5}}, u_max=u, k_max=u))
assert int(np.asarray(n_ovf)) == 0 and int(np.asarray(n_conf)) == 0
# the device digest is interner-scoped (per process); the CROSS-HOST
# convergence check is the canonical content digest + visible count
dig = (c.content_digest(merged), int(np.asarray(total_vis)))

# every host prints: digest of the device weave + host-level render
print("DIGEST", dig, flush=True)
print("EDN", len(c.causal_to_edn(merged)), flush=True)
"""


def test_two_process_mesh_sync_convergence():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    prog = _HOST_PROG.format(root=_ROOT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def spawn(i):
        return subprocess.Popen(
            [sys.executable, "-c", prog, str(i), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env)

    procs = []
    outs = []
    try:
        h0 = spawn(0)
        procs.append(h0)
        # wait for the server socket before spawning the client (the
        # client also retries, but this removes the race outright)
        first = h0.stdout.readline()
        assert first.strip() == "LISTENING", first
        procs.append(spawn(1))
        for p in procs:
            out, err = p.communicate(timeout=420)
            assert p.returncode == 0, err[-2000:]
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    digests = [l for o in outs for l in o.splitlines()
               if l.startswith("DIGEST")]
    edns = [l for o in outs for l in o.splitlines()
            if l.startswith("EDN")]
    assert len(digests) == 2 and digests[0] == digests[1], digests
    assert len(edns) == 2 and edns[0] == edns[1], edns
