"""Copied from the system under test (net/packet.py, net/socket.py,
apps/base.py, core/rng.py) into the benchmark, so that the plain
reference imports nothing of the program: the packet word layout, the
TCP states and control bits, the app kinds and the random-stream
domains. Values only."""

PKT_WORDS = 13
(SRC, DST, SPORT, DPORT, FLAGS, SEQ, ACK, WND, LEN, AUX, UID,
 APP, STATUS) = range(13)

DS_CREATED = 1 << 0
DS_RETRANS = 1 << 1

PROTO_MASK = 0xFF
PROTO_TCP = 6
PROTO_UDP = 17

F_SYN = 1 << 8
F_ACK = 1 << 9
F_FIN = 1 << 10
F_RST = 1 << 11

TCPS_CLOSED = 0
TCPS_LISTEN = 1
TCPS_SYN_SENT = 2
TCPS_SYN_RECEIVED = 3
TCPS_ESTABLISHED = 4
TCPS_FIN_WAIT_1 = 5
TCPS_FIN_WAIT_2 = 6
TCPS_CLOSE_WAIT = 7
TCPS_CLOSING = 8
TCPS_LAST_ACK = 9
TCPS_TIME_WAIT = 10

CTL_SYN = 1
CTL_SYNACK = 2
CTL_ACKNOW = 4
CTL_FIN = 8
CTL_RST = 16

APP_NULL = 0
APP_PING = 1
APP_PING_SERVER = 2
APP_PHOLD = 3
APP_TGEN = 4
APP_BULK = 5
APP_BULK_SERVER = 6
APP_HOSTED = 7
APP_GOSSIP = 8
APP_SOCKS_CLIENT = 9
APP_SOCKS_PROXY = 10

DOMAIN_HOST = 1
DOMAIN_DROP = 2
