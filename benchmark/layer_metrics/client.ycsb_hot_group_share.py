"""Share of the window's operations that went to the busiest group:
the skew that was really offered. YCSB's scrambled Zipfian offers the
hottest group 0.038 whatever the seed (`offered_hot_group_share` in the
run's client numbers); operation i's group is fixed by the stream
whichever client draws it, so a convoy behind the hot lane changes the
rate and not this share."""


def read(run):
    return run.client.get("client.ycsb_hot_group_share")
