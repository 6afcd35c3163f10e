// Native OTLP/HTTP front door — the zero-Python per-payload ingest
// acceptor (ISSUE 19 tentpole; ROADMAP item 3's "front end is the
// wall" seam).
//
// A one-core CPU runner showed the pooled decode engine flat at
// ~6.1-6.2M spans/s across 1-4 workers because every byte traversed
// the Python http.server receiver before on_payload could hand it to
// the pool.
// This translation unit owns the whole socket→scan path natively:
//
//   accept → HTTP/1.1 framing (Content-Length, 413 oversize cap,
//   chunked rejection) → recv() DIRECTLY into a recycled native body
//   buffer → enqueue an (id, ptr, len) ticket for the Python pump →
//   verdict comes back via otd_fd_respond → canned response bytes on
//   the wire → buffer recycled for the connection's next request.
//
// No Python object is created, copied or touched per payload on this
// path: the pump (runtime/frontdoor.py) drains tickets in BATCHES
// (one GIL-released otd_fd_next call per batch) and the decode scans
// the buffers in place via the existing otd_decode_otlp_many pointer
// ABI. Python keeps only the control plane — the 429/413/400 verdict
// vocabulary decisions that need pipeline state (saturation hints, the
// DecodeTicket per-request decode verdicts), /healthz wiring, metrics
// and graceful drain — exactly the split runtime/otlp.py documents.
//
// Concurrency model: one acceptor thread + one thread per live
// connection (capped; a keep-alive OTLP exporter holds few
// connections, so thread-per-conn buys simplicity without an epoll
// state machine). A connection has AT MOST one request in flight —
// pipelined bytes wait buffered until the current verdict is written,
// which also keeps responses in request order as HTTP/1.1 requires.
//
// Buffer ownership rule (the safety contract with the pump): once a
// ticket is handed out by otd_fd_next, the body buffer belongs to
// Python until otd_fd_respond(id) — the connection thread blocks on
// the verdict condvar and never touches (or recycles) the buffer in
// between. Tickets still queued at stop time are answered 503
// natively, so no buffer is ever abandoned while borrowed.
//
// Thread/GIL contract matches ingest.cc: every export here is called
// through ctypes.CDLL (GIL released for the call's duration), touches
// only raw C memory, and the server's own threads never see a Python
// object.

#include <arpa/inet.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <deque>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

namespace {

using Clock = std::chrono::steady_clock;

// Signal kinds a ticket carries (the pump routes on these: traces go
// to the decode pool's pointer path, metrics/logs — scrape-cadence
// traffic, exempt from the saturation gate like runtime/otlp.py —
// take the Python decoders).
constexpr int32_t kKindTraces = 0;
constexpr int32_t kKindMetrics = 1;
constexpr int32_t kKindLogs = 2;

constexpr size_t kMaxHeaderBytes = 8192;
constexpr size_t kReadChunk = 65536;
// Body buffers larger than this shrink back after a small request so
// one fat export doesn't pin its size onto an idle keep-alive conn.
constexpr size_t kShrinkAbove = 1 << 20;

// Native reject counters (the natively-decided verdicts; Python
// counts the pool-verdict rejects itself). Indices are the
// otd_fd_stats layout — keep in sync with runtime/native.py.
enum StatIdx {
  kStatAccepted = 0,
  kStatLiveConns = 1,
  kStatEnqueued = 2,
  kStatPending = 3,
  kStatBadLength = 4,
  kStatOversized = 5,
  kStatChunked = 6,
  kStatTruncated = 7,
  kStatDisconnect = 8,
  kStatOvercap = 9,
  kStatHealth = 10,
  kStatNotFound = 11,
  kStatBytesIn = 12,
  kStatResponded = 13,
  kStatCount = 14,
};

struct Server;

struct Conn {
  Server* srv = nullptr;
  // Atomic because the acceptor's reaper partitions on fd != -1 with
  // no lock; teardown (exchange → shutdown → close) and otd_fd_stop's
  // wake-up shutdown additionally serialize under verdict_mu so stop
  // can never shutdown() an fd number the kernel already recycled.
  std::atomic<int> fd{-1};
  std::thread thread;
  // Buffered reader state: bytes recv'd but not yet consumed (the
  // pipelining holdover).
  std::string rbuf;
  size_t rpos = 0;
  // The connection's single in-flight request.
  std::vector<uint8_t> body;
  int64_t req_id = -1;
  std::mutex verdict_mu;
  std::condition_variable verdict_cv;
  int32_t status = 0;  // 0 = pending
  int32_t retry_after = 0;
  bool done = false;
};

struct Ticket {
  int64_t id;
  int32_t kind;
  const uint8_t* ptr;
  int64_t len;
};

struct Server {
  int listen_fd = -1;
  int port = 0;
  int64_t max_body = 16 << 20;
  int32_t max_conns = 64;
  int64_t header_timeout_ms = 10000;
  std::thread acceptor;

  std::atomic<bool> quiesced{false};
  std::atomic<bool> stopping{false};
  std::atomic<int64_t> next_id{1};
  std::atomic<int64_t> stats[kStatCount]{};

  std::mutex mu;  // guards conns, ready, by_id
  std::condition_variable ready_cv;
  std::vector<Conn*> conns;
  std::deque<Ticket> ready;
  std::map<int64_t, Conn*> by_id;
};

std::mutex g_servers_mu;
std::map<int64_t, Server*> g_servers;
int64_t g_next_handle = 1;

Server* find_server(int64_t h) {
  std::lock_guard<std::mutex> lk(g_servers_mu);
  auto it = g_servers.find(h);
  return it == g_servers.end() ? nullptr : it->second;
}

bool send_all(int fd, const char* buf, size_t n) {
  size_t off = 0;
  while (off < n) {
    ssize_t w = ::send(fd, buf + off, n - off, MSG_NOSIGNAL);
    if (w <= 0) {
      if (w < 0 && errno == EINTR) continue;
      return false;
    }
    off += static_cast<size_t>(w);
  }
  return true;
}

const char* reason_phrase(int status) {
  switch (status) {
    case 200: return "OK";
    case 400: return "Bad Request";
    case 404: return "Not Found";
    case 413: return "Content Too Large";
    case 429: return "Too Many Requests";
    case 500: return "Internal Server Error";
    case 503: return "Service Unavailable";
    default: return "OK";
  }
}

// Canned response writer. 200 carries the empty-protobuf success body
// the Python receiver sends (Content-Type + zero-length body); every
// other status is a bare status + Content-Length: 0 (+ optional
// Retry-After / Connection: close) — clients compare status codes,
// not server vanity headers.
bool write_response(int fd, int status, int retry_after, bool close_conn) {
  char buf[256];
  int n = snprintf(buf, sizeof(buf), "HTTP/1.1 %d %s\r\n", status,
                   reason_phrase(status));
  if (status == 200) {
    n += snprintf(buf + n, sizeof(buf) - n,
                  "Content-Type: application/x-protobuf\r\n");
  }
  if (retry_after > 0) {
    n += snprintf(buf + n, sizeof(buf) - n, "Retry-After: %d\r\n",
                  retry_after);
  }
  if (close_conn) {
    n += snprintf(buf + n, sizeof(buf) - n, "Connection: close\r\n");
  }
  n += snprintf(buf + n, sizeof(buf) - n, "Content-Length: 0\r\n\r\n");
  return send_all(fd, buf, static_cast<size_t>(n));
}

// recv() more bytes into the connection's read buffer. Returns >0 on
// progress, 0 on orderly EOF, <0 on error/timeout. `deadline` bounds
// the TOTAL wait (the slowloris guard: SO_RCVTIMEO alone resets per
// byte trickled).
int fill_rbuf(Conn* c, Clock::time_point deadline) {
  if (Clock::now() >= deadline) return -1;
  char tmp[kReadChunk];
  ssize_t r = ::recv(c->fd.load(), tmp, sizeof(tmp), 0);
  if (r > 0) {
    c->rbuf.append(tmp, static_cast<size_t>(r));
    c->srv->stats[kStatBytesIn] += r;
    return static_cast<int>(r);
  }
  if (r == 0) return 0;
  if (errno == EINTR) return 1;  // retryable, counts as progress-less ok
  return -1;
}

// Case-insensitive header lookup inside the raw header block
// [hdr_begin, hdr_end). Returns the trimmed value or "".
std::string header_value(const std::string& head, const char* name) {
  size_t nlen = strlen(name);
  size_t pos = 0;
  while (pos < head.size()) {
    size_t eol = head.find("\r\n", pos);
    if (eol == std::string::npos) eol = head.size();
    if (eol - pos > nlen && head[pos + nlen] == ':') {
      bool match = true;
      for (size_t i = 0; i < nlen; i++) {
        if (tolower(static_cast<unsigned char>(head[pos + i])) !=
            tolower(static_cast<unsigned char>(name[i]))) {
          match = false;
          break;
        }
      }
      if (match) {
        size_t v = pos + nlen + 1;
        while (v < eol && (head[v] == ' ' || head[v] == '\t')) v++;
        size_t e = eol;
        while (e > v && (head[e - 1] == ' ' || head[e - 1] == '\t')) e--;
        return head.substr(v, e - v);
      }
    }
    pos = eol + 2;
  }
  return "";
}

bool iequals(const std::string& a, const char* b) {
  size_t n = strlen(b);
  if (a.size() != n) return false;
  for (size_t i = 0; i < n; i++) {
    if (tolower(static_cast<unsigned char>(a[i])) !=
        tolower(static_cast<unsigned char>(b[i]))) {
      return false;
    }
  }
  return true;
}

// Parse a non-negative decimal. Returns -1 on malformed (the Python
// receiver's int(...) ValueError → 400 bad_length verdict).
int64_t parse_length(const std::string& s) {
  if (s.empty() || s.size() > 18) return -1;
  int64_t v = 0;
  for (char ch : s) {
    if (ch < '0' || ch > '9') return -1;
    v = v * 10 + (ch - '0');
  }
  return v;
}

// One request → verdict cycle. Returns false when the connection must
// close (error, Connection: close, or drain).
bool serve_one(Conn* c) {
  Server* s = c->srv;
  // Only this thread ever changes c->fd, so one load is stable for
  // the whole request cycle.
  const int fd = c->fd.load();
  auto deadline =
      Clock::now() + std::chrono::milliseconds(s->header_timeout_ms);

  // -- read the header block -------------------------------------------
  size_t hdr_end;
  for (;;) {
    hdr_end = c->rbuf.find("\r\n\r\n", c->rpos);
    if (hdr_end != std::string::npos) break;
    if (c->rbuf.size() - c->rpos > kMaxHeaderBytes) {
      s->stats[kStatBadLength]++;
      write_response(fd, 400, 0, true);
      return false;
    }
    int r = fill_rbuf(c, deadline);
    if (r < 0) {
      // Timeout (slowloris header trickle) or reset mid-headers: the
      // client is gone or hostile — release the thread, no response.
      if (c->rbuf.size() > c->rpos) s->stats[kStatDisconnect]++;
      return false;
    }
    if (r == 0) {
      // Orderly EOF. Between requests this is a clean keep-alive
      // close; mid-headers it is a disconnect.
      if (c->rbuf.size() > c->rpos) s->stats[kStatDisconnect]++;
      return false;
    }
  }
  std::string head = c->rbuf.substr(c->rpos, hdr_end - c->rpos);
  size_t body_start = hdr_end + 4;

  // -- request line ----------------------------------------------------
  size_t line_end = head.find("\r\n");
  std::string line =
      head.substr(0, line_end == std::string::npos ? head.size() : line_end);
  size_t sp1 = line.find(' ');
  size_t sp2 = line.find(' ', sp1 == std::string::npos ? 0 : sp1 + 1);
  if (sp1 == std::string::npos || sp2 == std::string::npos) {
    s->stats[kStatBadLength]++;
    write_response(fd, 400, 0, true);
    return false;
  }
  std::string method = line.substr(0, sp1);
  std::string path = line.substr(sp1 + 1, sp2 - sp1 - 1);
  size_t q = path.find('?');
  if (q != std::string::npos) path.resize(q);
  bool keep_alive = true;
  std::string conn_hdr = header_value(head, "Connection");
  if (iequals(conn_hdr, "close")) keep_alive = false;

  if (method == "GET") {
    // Erase the consumed request like the POST path does — advancing
    // rpos alone would let a keep-alive /healthz prober grow rbuf
    // without bound.
    c->rbuf.erase(0, body_start);
    c->rpos = 0;
    if (path == "/healthz") {
      s->stats[kStatHealth]++;
      write_response(fd, 200, 0, !keep_alive);
    } else {
      s->stats[kStatNotFound]++;
      write_response(fd, 404, 0, !keep_alive);
    }
    return keep_alive;
  }
  if (method != "POST") {
    s->stats[kStatNotFound]++;
    write_response(fd, 404, 0, true);
    return false;
  }

  // -- framing verdicts (native; zero Python) --------------------------
  std::string te = header_value(head, "Transfer-Encoding");
  if (!te.empty() && !iequals(te, "identity")) {
    // Chunked (or any exotic coding) is refused outright: the framing
    // the zero-copy body read depends on is Content-Length. 400 with
    // close — the chunked body bytes must not be parsed as a next
    // request.
    s->stats[kStatChunked]++;
    write_response(fd, 400, 0, true);
    return false;
  }
  std::string cl = header_value(head, "Content-Length");
  int64_t length = cl.empty() ? 0 : parse_length(cl);
  if (length < 0) {
    s->stats[kStatBadLength]++;
    write_response(fd, 400, 0, true);
    return false;
  }
  if (length > s->max_body) {
    // Oversized: refuse WITHOUT reading the body (runtime/otlp.py's
    // exact contract — draining a multi-GB body to politely answer
    // 413 is itself a resource fault) and close so the unread
    // remainder can't be parsed as a next request.
    s->stats[kStatOversized]++;
    write_response(fd, 413, 0, true);
    return false;
  }

  int32_t kind = kKindTraces;
  if (path.size() >= 11 &&
      path.compare(path.size() - 11, 11, "/v1/metrics") == 0) {
    kind = kKindMetrics;
  } else if (path.size() >= 8 &&
             path.compare(path.size() - 8, 8, "/v1/logs") == 0) {
    kind = kKindLogs;
  }

  // -- body straight into the recycled native buffer -------------------
  c->body.resize(static_cast<size_t>(length));
  size_t have = std::min(c->rbuf.size() - body_start,
                         static_cast<size_t>(length));
  memcpy(c->body.data(), c->rbuf.data() + body_start, have);
  // Consume header + the body prefix; keep any pipelined tail.
  c->rbuf.erase(0, body_start + have);
  c->rpos = 0;
  size_t filled = have;
  // Total-deadline for the body too (SO_RCVTIMEO alone resets per
  // trickled byte — the slowloris guard must cover both phases):
  // header-timeout grace plus a floor transfer rate of ~8 KiB/s, so a
  // one-byte-per-9s trickler is bounded while a slow legitimate
  // exporter on a thin link is not cut off.
  auto body_deadline = Clock::now() + std::chrono::milliseconds(
                           s->header_timeout_ms + length / 8);
  while (filled < static_cast<size_t>(length)) {
    if (Clock::now() >= body_deadline) {
      s->stats[kStatDisconnect]++;
      return false;
    }
    ssize_t r = ::recv(fd, c->body.data() + filled,
                       static_cast<size_t>(length) - filled, 0);
    if (r > 0) {
      filled += static_cast<size_t>(r);
      s->stats[kStatBytesIn] += r;
      continue;
    }
    if (r < 0 && errno == EINTR) continue;
    if (r == 0) {
      // Truncated frame: the client promised more bytes than it sent
      // (died mid-upload). 4xx, not a crash — otlp.py's verdict.
      s->stats[kStatTruncated]++;
      write_response(fd, 400, 0, true);
    } else {
      // Timeout or reset mid-body: nothing to answer.
      s->stats[kStatDisconnect]++;
    }
    return false;
  }

  if (s->quiesced.load() || s->stopping.load()) {
    // Draining: no new work enters the pump. 503 is the OTLP
    // retryable status — the exporter resends to the successor.
    write_response(fd, 503, 1, true);
    return false;
  }

  // -- enqueue the ticket and wait for the pump's verdict --------------
  int64_t id = s->next_id.fetch_add(1);
  {
    std::lock_guard<std::mutex> lk(c->verdict_mu);
    c->req_id = id;
    c->status = 0;
    c->retry_after = 0;
    c->done = false;
  }
  {
    // The stopping re-check MUST happen under s->mu: otd_fd_stop sets
    // stopping before taking s->mu for its ready/by_id 503 flush, so a
    // ticket either lands before the flush (and is flushed) or the
    // check here observes stopping and refuses — no ticket can be
    // enqueued after the flush with nobody left to answer it (which
    // would strand this thread on verdict_cv and hang stop's join).
    std::unique_lock<std::mutex> lk(s->mu);
    if (s->stopping.load()) {
      lk.unlock();
      write_response(fd, 503, 1, true);
      return false;
    }
    s->by_id[id] = c;
    s->ready.push_back(Ticket{id, kind, c->body.data(),
                              static_cast<int64_t>(length)});
  }
  s->stats[kStatEnqueued]++;
  s->stats[kStatPending]++;
  s->ready_cv.notify_one();

  int32_t status, retry_after;
  {
    // The buffer is Python's until the verdict lands: wait without a
    // deadline (otd_fd_stop answers every queued ticket 503, so this
    // cannot outlive the server).
    std::unique_lock<std::mutex> lk(c->verdict_mu);
    c->verdict_cv.wait(lk, [c] { return c->done; });
    status = c->status;
    retry_after = c->retry_after;
  }
  s->stats[kStatPending]--;
  s->stats[kStatResponded]++;

  if (c->body.capacity() > kShrinkAbove &&
      static_cast<size_t>(length) < kShrinkAbove / 16) {
    std::vector<uint8_t>().swap(c->body);
  }
  bool close_now = !keep_alive || s->stopping.load();
  if (!write_response(fd, status, retry_after, close_now)) {
    s->stats[kStatDisconnect]++;
    return false;
  }
  return !close_now;
}

void conn_loop(Conn* c) {
  // Per-recv bound so a dead peer can't pin the thread; the overall
  // header deadline in serve_one handles the trickle case.
  const int fd = c->fd.load();
  struct timeval tv;
  tv.tv_sec = static_cast<time_t>(c->srv->header_timeout_ms / 1000);
  tv.tv_usec =
      static_cast<suseconds_t>((c->srv->header_timeout_ms % 1000) * 1000);
  setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  while (!c->srv->stopping.load()) {
    if (!serve_one(c)) break;
  }
  {
    // Publish -1 and close under verdict_mu: otd_fd_stop's wake-up
    // shutdown() takes the same mutex, so it can never race this
    // close and hit a kernel-recycled fd number.
    std::lock_guard<std::mutex> lk(c->verdict_mu);
    c->fd.store(-1);
    ::shutdown(fd, SHUT_RDWR);
    ::close(fd);
  }
  c->srv->stats[kStatLiveConns]--;
}

void accept_loop(Server* s) {
  for (;;) {
    struct sockaddr_in addr;
    socklen_t alen = sizeof(addr);
    int fd = ::accept(s->listen_fd, reinterpret_cast<sockaddr*>(&addr),
                      &alen);
    if (fd < 0) {
      if (errno == EINTR) continue;
      return;  // listener closed: quiesce/stop
    }
    if (s->stopping.load() || s->quiesced.load()) {
      ::close(fd);
      return;
    }
    if (s->stats[kStatLiveConns].load() >= s->max_conns) {
      // Connection cap: retryable refusal, never an accept backlog
      // that turns into unbounded thread growth.
      s->stats[kStatOvercap]++;
      write_response(fd, 503, 1, true);
      ::close(fd);
      continue;
    }
    s->stats[kStatAccepted]++;
    s->stats[kStatLiveConns]++;
    // Reap finished connections (fd already -1): join + delete here so
    // a long-lived server doesn't accumulate dead Conn objects.
    {
      std::vector<Conn*> dead;
      {
        std::lock_guard<std::mutex> lk(s->mu);
        auto alive_end = std::partition(
            s->conns.begin(), s->conns.end(),
            [](Conn* c) { return c->fd != -1; });
        dead.assign(alive_end, s->conns.end());
        s->conns.erase(alive_end, s->conns.end());
      }
      for (Conn* c : dead) {
        if (c->thread.joinable()) c->thread.join();
        delete c;
      }
    }
    Conn* c = new Conn();
    c->srv = s;
    c->fd = fd;
    {
      std::lock_guard<std::mutex> lk(s->mu);
      s->conns.push_back(c);
    }
    c->thread = std::thread(conn_loop, c);
  }
}

void respond_locked_ticket(Server* s, const Ticket& t, int32_t status,
                           int32_t retry_after) {
  Conn* c;
  {
    std::lock_guard<std::mutex> lk(s->mu);
    auto it = s->by_id.find(t.id);
    if (it == s->by_id.end()) return;
    c = it->second;
    s->by_id.erase(it);
  }
  {
    std::lock_guard<std::mutex> lk(c->verdict_mu);
    c->status = status;
    c->retry_after = retry_after;
    c->done = true;
  }
  c->verdict_cv.notify_one();
}

}  // namespace

extern "C" {

// Start a front door on `port` (0 = ephemeral). Returns a handle
// (>0), or -1 when the socket could not be bound.
int64_t otd_fd_start(int32_t port, int64_t max_body, int32_t max_conns,
                     int64_t header_timeout_ms) {
  int fd = ::socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  int one = 1;
  setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  struct sockaddr_in addr;
  memset(&addr, 0, sizeof(addr));
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_ANY);
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (::bind(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) < 0 ||
      ::listen(fd, 128) < 0) {
    ::close(fd);
    return -1;
  }
  socklen_t alen = sizeof(addr);
  getsockname(fd, reinterpret_cast<sockaddr*>(&addr), &alen);

  Server* s = new Server();
  s->listen_fd = fd;
  s->port = ntohs(addr.sin_port);
  s->max_body = max_body > 0 ? max_body : (16 << 20);
  s->max_conns = max_conns > 0 ? max_conns : 64;
  s->header_timeout_ms = header_timeout_ms > 0 ? header_timeout_ms : 10000;
  s->acceptor = std::thread(accept_loop, s);

  std::lock_guard<std::mutex> lk(g_servers_mu);
  int64_t h = g_next_handle++;
  g_servers[h] = s;
  return h;
}

int32_t otd_fd_port(int64_t h) {
  Server* s = find_server(h);
  return s ? s->port : -1;
}

// Pop up to `max_n` complete request tickets, blocking up to
// `timeout_ms`. Fills ids/kinds/ptrs/lens. Returns the count (0 on
// timeout) or -1 once the server is stopping and the queue is empty —
// the pump's exit signal. Called with the GIL released (ctypes.CDLL).
int64_t otd_fd_next(int64_t h, int64_t* ids, int32_t* kinds,
                    const uint8_t** ptrs, int64_t* lens, int64_t max_n,
                    int64_t timeout_ms) {
  Server* s = find_server(h);
  if (s == nullptr || max_n <= 0) return -1;
  std::unique_lock<std::mutex> lk(s->mu);
  if (s->ready.empty()) {
    s->ready_cv.wait_for(lk, std::chrono::milliseconds(timeout_ms),
                         [s] { return !s->ready.empty() ||
                                      s->stopping.load(); });
  }
  if (s->ready.empty()) return s->stopping.load() ? -1 : 0;
  int64_t n = 0;
  while (n < max_n && !s->ready.empty()) {
    const Ticket& t = s->ready.front();
    ids[n] = t.id;
    kinds[n] = t.kind;
    ptrs[n] = t.ptr;
    lens[n] = t.len;
    s->ready.pop_front();
    n++;
  }
  return n;
}

// Deliver the verdict for ticket `id`: the connection thread writes
// the canned response and recycles the buffer. retry_after <= 0
// omits the header. Returns 0 (unknown ids are a no-op: the conn may
// have died — its close path already counted the disconnect).
int32_t otd_fd_respond(int64_t h, int64_t id, int32_t status,
                       int32_t retry_after) {
  Server* s = find_server(h);
  if (s == nullptr) return -1;
  respond_locked_ticket(s, Ticket{id, 0, nullptr, 0}, status, retry_after);
  return 0;
}

void otd_fd_stats(int64_t h, int64_t* out) {
  Server* s = find_server(h);
  for (int i = 0; i < kStatCount; i++) {
    out[i] = s ? s->stats[i].load() : 0;
  }
}

// Stop accepting new connections/requests (graceful drain, phase 1).
// Already-enqueued tickets keep flowing to the pump; new requests on
// live connections answer 503.
void otd_fd_quiesce(int64_t h) {
  Server* s = find_server(h);
  if (s == nullptr) return;
  s->quiesced.store(true);
  ::shutdown(s->listen_fd, SHUT_RDWR);
}

// Full stop (phase 2): answer every still-queued ticket 503, wake the
// pump (otd_fd_next returns -1), shut every connection down and join
// all threads. The handle stays valid for stats reads; call after the
// Python pumps have drained their in-flight batches.
void otd_fd_stop(int64_t h) {
  Server* s = find_server(h);
  if (s == nullptr) return;
  s->quiesced.store(true);
  s->stopping.store(true);
  ::shutdown(s->listen_fd, SHUT_RDWR);
  s->ready_cv.notify_all();
  // Flush the ready queue with 503s so no connection thread waits on
  // a verdict that will never come (and no buffer stays borrowed);
  // the conn threads do the pending/responded accounting as usual.
  std::deque<Ticket> leftover;
  {
    std::lock_guard<std::mutex> lk(s->mu);
    leftover.swap(s->ready);
  }
  for (const Ticket& t : leftover) {
    respond_locked_ticket(s, t, 503, 1);
  }
  if (s->acceptor.joinable()) s->acceptor.join();
  ::close(s->listen_fd);
  // Any ticket the pump popped but never answered (a dead pump) gets
  // its 503 here — same lock order as respond (s->mu, then verdict).
  std::vector<int64_t> orphans;
  {
    std::lock_guard<std::mutex> lk(s->mu);
    for (const auto& kv : s->by_id) orphans.push_back(kv.first);
  }
  for (int64_t id : orphans) {
    respond_locked_ticket(s, Ticket{id, 0, nullptr, 0}, 503, 1);
  }
  std::vector<Conn*> conns;
  {
    std::lock_guard<std::mutex> lk(s->mu);
    conns.swap(s->conns);
  }
  for (Conn* c : conns) {
    std::lock_guard<std::mutex> lk(c->verdict_mu);
    // Under verdict_mu the conn thread's exchange(-1)+close teardown
    // cannot interleave, so this shutdown() can never hit an fd number
    // the kernel already recycled for another descriptor.
    int fd = c->fd.load();
    if (fd >= 0) ::shutdown(fd, SHUT_RDWR);
    // Belt-and-suspenders vs a stranded waiter: resolve, don't just
    // notify — a bare notify_all leaves the wait predicate (done)
    // false and the join below would hang forever. The enqueue-time
    // stopping re-check makes this unreachable in practice, but a
    // verdict the pump popped-and-dropped still lands here.
    if (!c->done) {
      c->status = 503;
      c->retry_after = 1;
      c->done = true;
    }
    c->verdict_cv.notify_all();
  }
  for (Conn* c : conns) {
    if (c->thread.joinable()) c->thread.join();
    delete c;
  }
}

}  // extern "C"
