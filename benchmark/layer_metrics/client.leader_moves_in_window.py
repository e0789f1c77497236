"""Groups whose leader when the window closed was not its leader when the
window opened. Nothing in the cell moves a leader on purpose: only
followers are replaced, so every move is an election that a replacement
(or a stall) caused."""


def read(run):
    return run.client.get("client.leader_moves_in_window")
