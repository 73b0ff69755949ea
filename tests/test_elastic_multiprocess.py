"""Multi-process elastic kill-and-resume (SURVEY C14, call stack (d)).

Composes the two tiers that were previously only proven separately
(test_multiprocess.py: 2-process rendezvous/training; test_elastic.py:
single-host crash→restart→resume): TWO supervised processes rendezvous over
``jax.distributed``; the coordinator's child hard-dies mid-run (fault
injection — the moral equivalent of SIGKILL); the surviving process's child
detects the peer loss through the coordination service and exits; each
host's supervisor restarts its child; the 2-process group re-forms and
training resumes from the last sharded checkpoint with no step duplicated
or lost. This is BASELINE config 5's "multi-node elastic" capability on
real process boundaries.
"""

import json
import os

from _mp_harness import free_port, rendezvous_env, run_workers


def test_multiprocess_kill_and_resume(tmp_path):
    env_base = rendezvous_env(tmp_path, free_port(), device_count=2)
    envs = []
    for pid in range(2):
        env = {**env_base, "FRL_TPU_PROCESS_ID": str(pid)}
        if pid == 0:
            # Kill the COORDINATOR's child: the harder failure mode — the
            # peer loses the coordination service itself, not just a member.
            env["FRL_FAULT_AT_STEP"] = "9"
        envs.append(env)
    rcs, outputs = run_workers("_elastic_worker.py", envs, timeout=280)
    for rc, out in zip(rcs, outputs):
        assert rc == 0, f"supervisor failed:\n{out[-3000:]}"

    # Each host's supervisor went through exactly one restart cycle: the
    # faulted child on host 0, the peer-loss exit on host 1.
    for out in outputs:
        assert "elastic: run completed after 1 restart(s)" in out, out[-3000:]
        # One process per chip: the supervising parent imported jax (config
        # only) but never brought a backend up — on the chip it would
        # otherwise hold the device its training child needs.
        assert "SUPERVISOR_BACKENDS=0" in out, out[-3000:]
    assert "fault injection: hard-exit" in outputs[0]
    # The survivor died to the coordination service noticing the dead peer,
    # not to the fault hook (it was never armed there).
    assert "fault injection" not in outputs[1]

    run_dir = os.path.join(str(tmp_path), "mnist_mlp")
    assert os.path.exists(os.path.join(run_dir, "fault_injected"))
    # Proof of resume-not-restart: metrics.jsonl (process-0-gated, append-
    # only across child generations) — run 1 logs steps 4 and 8, dies after
    # 9; run 2 restores the step-8 checkpoint and logs only 12.
    with open(os.path.join(run_dir, "metrics.jsonl")) as fh:
        steps = [json.loads(line)["step"] for line in fh]
    assert steps == [4, 8, 12], steps
    ckpt_steps = sorted(
        int(d) for d in os.listdir(os.path.join(run_dir, "ckpt")) if d.isdigit()
    )
    assert 12 in ckpt_steps


def test_multiprocess_shrink_to_survivors(tmp_path):
    """Smaller-slice continuation (SURVEY C14 "re-initialize (possibly
    smaller slice)", call stack (d) "re-rendezvous with surviving nodes"):
    the COORDINATOR host dies permanently (fault + zero restart budget);
    the surviving host's supervisor fails one full-size restart against the
    dead coordinator, reads the membership heartbeats, shrinks to a
    1-process world with itself as rank 0, and finishes the run from the
    last sharded checkpoint — no step duplicated or lost."""
    env_base = rendezvous_env(tmp_path, free_port(), device_count=2)
    envs = []
    for pid in range(2):
        env = {
            **env_base,
            "FRL_TPU_PROCESS_ID": str(pid),
            # Bound the dead-coordinator rendezvous: the shrink decision
            # happens after this timeout fails the full-size restart.
            "FRL_TPU_INIT_TIMEOUT_S": "15",
            "FRL_TPU_HOST_ADDRESS": "127.0.0.1",
        }
        if pid == 0:
            env["FRL_FAULT_AT_STEP"] = "9"
        envs.append(env)
    rcs, outputs = run_workers("_elastic_shrink_worker.py", envs, timeout=280)

    # Host 0: the fault's exit code surfaces (budget 0, never restarted).
    assert rcs[0] == 43, f"coordinator supervisor:\n{outputs[0][-3000:]}"
    assert "fault injection: hard-exit" in outputs[0]
    # Host 1: survived, shrank, completed.
    assert rcs[1] == 0, f"survivor supervisor:\n{outputs[1][-3000:]}"
    assert "elastic: shrinking from 2 to 1" in outputs[1], outputs[1][-3000:]
    assert "elastic: run completed" in outputs[1]
    assert "fault injection" not in outputs[1]

    run_dir = os.path.join(str(tmp_path), "mnist_mlp")
    # Proof of resume-not-restart across the topology change: run 1
    # (2 hosts, host 0 was rank 0) logs steps 4 and 8 then dies after 9;
    # the shrunk run (host 1 as the new rank 0) restores the step-8
    # checkpoint and logs only 12 — same append-only metrics.jsonl.
    with open(os.path.join(run_dir, "metrics.jsonl")) as fh:
        steps = [json.loads(line)["step"] for line in fh]
    assert steps == [4, 8, 12], steps
    ckpt_steps = sorted(
        int(d) for d in os.listdir(os.path.join(run_dir, "ckpt")) if d.isdigit()
    )
    assert 12 in ckpt_steps
    # The dead host retired its heartbeat; the survivor's is the only one
    # left (it retires on clean exit too — directory may also be empty).
    members = os.listdir(os.path.join(run_dir, "members"))
    assert "host_0.json" not in members, members


def test_multiprocess_grow_back_after_shrink(tmp_path):
    """Re-admission after a shrink (VERDICT r4 #7): the coordinator host
    dies, the survivor shrinks to a 1-process world and keeps training;
    the dead host then comes back (repaired / false-positive eviction).
    The survivor's grow watcher must preempt its child (SIGTERM →
    checkpoint → clean exit) and re-form the 2-process world — ranks
    remapped back, Orbax resharding restore — and BOTH hosts finish the
    run, no step lost or duplicated, no operator action."""
    env_base = rendezvous_env(tmp_path, free_port(), device_count=2)
    envs = []
    for pid in range(2):
        env = {
            **env_base,
            "FRL_TPU_PROCESS_ID": str(pid),
            "FRL_TPU_INIT_TIMEOUT_S": "15",
            "FRL_TPU_HOST_ADDRESS": "127.0.0.1",
            # Stretch steps so the revival lands while the shrunken world
            # is still mid-run (synthetic steps are sub-ms otherwise).
            "FRL_STEP_DELAY_S": "0.25",
        }
        if pid == 0:
            env["FRL_FAULT_AT_STEP"] = "9"
        envs.append(env)
    rcs, outputs = run_workers("_elastic_grow_worker.py", envs, timeout=420)

    # Host 0 revived and its second supervisor completed the run.
    assert rcs[0] == 0, f"revived coordinator:\n{outputs[0][-3000:]}"
    # Host 1 shrank, then grew back, then completed.
    assert rcs[1] == 0, f"survivor supervisor:\n{outputs[1][-3000:]}"
    assert "elastic: shrinking from 2 to 1" in outputs[1], outputs[1][-3000:]
    assert "preempting child to re-form" in outputs[1], outputs[1][-3000:]
    assert "elastic: growing from 1 to 2" in outputs[1], outputs[1][-3000:]
    assert "elastic: run completed" in outputs[1]

    run_dir = os.path.join(str(tmp_path), "mnist_mlp")
    # No step lost or duplicated across BOTH topology changes: the
    # append-only metrics.jsonl (written by whichever host is rank 0 at
    # the time) must be non-decreasing and end exactly at total_steps.
    with open(os.path.join(run_dir, "metrics.jsonl")) as fh:
        steps = [json.loads(line)["step"] for line in fh]
    assert steps == sorted(steps), steps
    assert steps[-1] == 120 and steps.count(120) == 1, steps
    ckpt_steps = sorted(
        int(d) for d in os.listdir(os.path.join(run_dir, "ckpt")) if d.isdigit()
    )
    assert 120 in ckpt_steps
