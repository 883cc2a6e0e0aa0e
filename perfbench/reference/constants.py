"""Copied from the system under test (core/constants.py) so that the plain
reference imports nothing of the program."""

from .simtime import SIMTIME_ONE_MILLISECOND, SIMTIME_ONE_SECOND

# --- Link layer / packet sizes ---
MTU = 1500
HEADER_SIZE_UDPIPETH = 42   # Ethernet + IP + UDP header bytes
HEADER_SIZE_TCPIPETH = 66   # Ethernet + IP + TCP header bytes (with options)
TCP_MSS = MTU - HEADER_SIZE_TCPIPETH    # 1434 payload bytes per full segment
UDP_MAX_PAYLOAD = MTU - HEADER_SIZE_UDPIPETH
DATAGRAM_MAX_SIZE = 65507

# --- Socket buffers (bytes) ---
SEND_BUFFER_SIZE = 131072
RECV_BUFFER_SIZE = 174760
SEND_BUFFER_MIN_SIZE = 16384
RECV_BUFFER_MIN_SIZE = 87380
TCP_WMEM_MAX = 4194304
TCP_RMEM_MAX = 6291456
PIPE_BUFFER_SIZE = 65536

# --- TCP timers (reference values are in milliseconds) ---
TCP_RTO_INIT = 1000 * SIMTIME_ONE_MILLISECOND
TCP_RTO_MIN = 200 * SIMTIME_ONE_MILLISECOND
TCP_RTO_MAX = 1_200_000 * SIMTIME_ONE_MILLISECOND
TCP_CLOSE_TIMER_DELAY = 60 * SIMTIME_ONE_SECOND

# --- NIC model ---
# Received packets are drained from the NIC in batches covering this much
# simulated time (reference CONFIG_RECEIVE_BATCH_TIME, shd-definitions.h:201).
RECEIVE_BATCH_TIME = 10 * SIMTIME_ONE_MILLISECOND
# Default NIC buffer size in bytes (reference --interface-buffer option
# default, shd-options.c).
INTERFACE_BUFFER_SIZE = 1024000

# --- Port allocation (reference shd-definitions.h MIN_RANDOM_PORT) ---
MIN_RANDOM_PORT = 10000
MAX_PORT = 65535

# Default window for the conservative lookahead barrier when the topology
# provides no minimum latency (reference shd-master.c:123 falls back to 10ms).
DEFAULT_MIN_TIME_JUMP = 10 * SIMTIME_ONE_MILLISECOND
