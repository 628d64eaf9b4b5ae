"""worker loop: the share of the window's chunk time that the slowest
tenth of the chunks spent beyond the mean of the rest — what the
trimming in ``train_throughput`` leaves out (host clock, every chunk
of the window but those the profiler ran in).  By the chunk times the
runs of PR 23 logged: about 1.3e-3 for Mistral (one chunk of every run
takes 52 ms longer), under 3e-4 for ResNet, and 0.03 to 0.07 in the
runs of call B in which one chunk stalled for 1.2 to 2.6 s."""


def read(facts):
    return facts.get("stall_share")
