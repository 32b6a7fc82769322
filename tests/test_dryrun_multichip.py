"""``__graft_entry__.dryrun_multichip``: every trainer's step over the
virtual 8-device mesh, tiny shapes — GraphSAGE, MLP, the GraphTransformer
in gather mode, in ring mode and tensor-parallel."""

import __graft_entry__ as graft


def test_dryrun_multichip_runs_to_its_end(capsys):
    graft.dryrun_multichip(8)
    assert "dryrun_multichip ok: 8-device mesh" in capsys.readouterr().out
