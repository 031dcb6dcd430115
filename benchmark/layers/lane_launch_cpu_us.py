"""Link, the lane (transport/device_link.py): the CPU time (``time.thread_time_ns``) of
``lane_launch_us``'s stage on the writer's thread, one program in four. Mean of the busiest
link's ``device_link_<n>_lane_launch_cpu_us`` recorder over the window, a row a
paired lane program; ``None`` on a program without the lane."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "lane_launch_cpu_us")
