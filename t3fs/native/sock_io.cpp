// A mover: one thread of a connection (t3fs/net/conn.py) that moves one
// large frame body at a time between the connection's non-blocking
// stream socket and memory, whole, outside the interpreter, so a body
// costs the event loop a hand-over and a wake-up, never the copy, and
// costs the mover no interpreter lock at all.  poll() waits for the
// socket in between; the loop hears that a body is done through an
// eventfd.  t3fs_mover_stop() shuts the socket down, which wakes a body
// in flight (a receive sees the end of the stream, a send EPIPE), and
// joins the thread before it frees the mover and closes its descriptors.

#include <cerrno>
#include <condition_variable>
#include <cstddef>
#include <cstdint>
#include <mutex>
#include <new>
#include <poll.h>
#include <pthread.h>
#include <sched.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <thread>
#include <unistd.h>

namespace {

// 0 once fd reports an event for ev (ready, hung up or in error: the next
// transfer says which), else the errno of poll
int wait_for(int fd, short ev) {
  pollfd p{fd, ev, 0};
  for (;;) {
    if (poll(&p, 1, -1) > 0) return 0;
    if (errno != EINTR) return errno;
  }
}

// Receives exactly n bytes into buf: 0; -1 if the stream ends first;
// else the errno of the failed call.
int recv_exact(int fd, uint8_t* buf, uint64_t n) {
  uint64_t got = 0;
  while (got < n) {
    ssize_t r = recv(fd, buf + got, n - got, 0);
    if (r > 0) {
      got += static_cast<uint64_t>(r);
    } else if (r == 0) {
      return -1;
    } else if (errno == EAGAIN || errno == EWOULDBLOCK) {
      if (int e = wait_for(fd, POLLIN)) return e;
    } else if (errno != EINTR) {
      return errno;
    }
  }
  return 0;
}

// Sends the na bytes at a, then the nb bytes at b, whole: 0, else the
// errno of the failed call.
int send_all(int fd, const uint8_t* a, uint64_t na, const uint8_t* b,
             uint64_t nb) {
  iovec iov[2] = {{const_cast<uint8_t*>(a), na},
                  {const_cast<uint8_t*>(b), nb}};
  int i = 0;
  while (i < 2) {
    if (iov[i].iov_len == 0) {
      ++i;
      continue;
    }
    msghdr m{};
    m.msg_iov = iov + i;
    m.msg_iovlen = 2 - i;
    ssize_t r = sendmsg(fd, &m, MSG_NOSIGNAL);
    if (r < 0) {
      if (errno == EAGAIN || errno == EWOULDBLOCK) {
        if (int e = wait_for(fd, POLLOUT)) return e;
      } else if (errno != EINTR) {
        return errno;
      }
      continue;
    }
    for (size_t left = static_cast<size_t>(r); left > 0;) {
      size_t t = left < iov[i].iov_len ? left : iov[i].iov_len;
      iov[i].iov_base = static_cast<uint8_t*>(iov[i].iov_base) + t;
      iov[i].iov_len -= t;
      left -= t;
      if (iov[i].iov_len == 0) ++i;
    }
  }
  return 0;
}

enum class Op { kIdle, kRecv, kSend, kStop };

struct Mover {
  int fd = -1;   // a dup of the connection's socket, owned
  int efd = -1;  // counts finished bodies
  std::mutex mu;
  std::condition_variable cv;
  Op op = Op::kIdle;  // the body posted and not yet finished
  uint8_t* a = nullptr;
  uint64_t na = 0;
  const uint8_t* b = nullptr;
  uint64_t nb = 0;
  int result = 0;
  std::thread th;
};

void run(Mover* m) {
  pthread_setname_np(pthread_self(), "t3fs-mover");
  // a batch thread does not preempt the thread that woke it: posting a
  // body must not take the event loop's CPU away from it for the length
  // of the copy (best effort: the policy needs no privilege)
  sched_param sp{};
  pthread_setschedparam(pthread_self(), SCHED_BATCH, &sp);
  std::unique_lock<std::mutex> lk(m->mu);
  for (;;) {
    m->cv.wait(lk, [m] { return m->op != Op::kIdle; });
    if (m->op == Op::kStop) return;
    Op op = m->op;
    uint8_t* a = m->a;
    uint64_t na = m->na;
    const uint8_t* b = m->b;
    uint64_t nb = m->nb;
    lk.unlock();
    int r = op == Op::kRecv ? recv_exact(m->fd, a, na)
                            : send_all(m->fd, a, na, b, nb);
    lk.lock();
    m->result = r;
    if (m->op != Op::kStop) m->op = Op::kIdle;
    uint64_t one = 1;
    ssize_t w = write(m->efd, &one, sizeof one);
    (void)w;  // a counter of 1 cannot overflow the eventfd
  }
}

void post(Mover* m, Op op, uint8_t* a, uint64_t na, const uint8_t* b,
          uint64_t nb) {
  {
    std::lock_guard<std::mutex> lk(m->mu);
    m->op = op;
    m->a = a;
    m->na = na;
    m->b = b;
    m->nb = nb;
  }
  m->cv.notify_one();
}

}  // namespace

extern "C" {

// Starts a mover on fd, which it owns from here on (it closes fd even
// when it fails to start): the mover, or null.
void* t3fs_mover_start(int fd) {
  Mover* m = new (std::nothrow) Mover;
  int efd = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
  if (m == nullptr || efd < 0) {
    delete m;
    if (efd >= 0) close(efd);
    close(fd);
    return nullptr;
  }
  m->fd = fd;
  m->efd = efd;
  try {
    m->th = std::thread(run, m);
  } catch (...) {
    close(fd);
    close(efd);
    delete m;
    return nullptr;
  }
  return m;
}

// Readable once a posted body is finished; t3fs_mover_result resets it.
int t3fs_mover_efd(void* h) { return static_cast<Mover*>(h)->efd; }

// Posts a receive of exactly n bytes into buf.  One body at a time: post
// the next only after t3fs_mover_result has read this one's.
void t3fs_mover_recv(void* h, uint8_t* buf, uint64_t n) {
  post(static_cast<Mover*>(h), Op::kRecv, buf, n, nullptr, 0);
}

// Posts a send of the na bytes at a, then the nb bytes at b.
void t3fs_mover_send(void* h, const uint8_t* a, uint64_t na,
                     const uint8_t* b, uint64_t nb) {
  post(static_cast<Mover*>(h), Op::kSend, const_cast<uint8_t*>(a), na, b,
       nb);
}

// The finished body's result (0; -1 at the end of the stream; an errno),
// once the eventfd is readable.
int t3fs_mover_result(void* h) {
  Mover* m = static_cast<Mover*>(h);
  uint64_t n;
  ssize_t r = read(m->efd, &n, sizeof n);
  (void)r;
  std::lock_guard<std::mutex> lk(m->mu);
  return m->result;
}

// Shuts the socket down (both ways: a body in flight ends now), joins the
// thread, closes both descriptors and frees the mover.  Buffers posted to
// it are free once this returns.
void t3fs_mover_stop(void* h) {
  Mover* m = static_cast<Mover*>(h);
  {
    std::lock_guard<std::mutex> lk(m->mu);
    m->op = Op::kStop;
  }
  m->cv.notify_one();
  shutdown(m->fd, SHUT_RDWR);
  m->th.join();
  close(m->fd);
  close(m->efd);
  delete m;
}

}  // extern "C"
